"""Two-process near-real-time ingest: detector host -> socket -> consumer.

The paper's Fig. 7 topology split across OS processes, the first step toward
its beamline/cluster deployment (and its ZeroMQ future-work item):

  producer process                          consumer process (this one)
  ----------------                          ---------------------------
  DetectorSource (frame simulator)          Broker (in-memory logs)
    -> IngestRunner (block backpressure)    BrokerServer on a socket
    -> RemoteBroker ──── TCP/Unix ────────▶   -> StreamingContext micro-batches
       (lag measured against the                -> per-batch photon statistics
        offsets the consumer committed          -> commits pushed broker-side,
        broker-side)                               closing the backpressure loop

The producer never shares memory with the consumer: every frame crosses the
length-prefixed socket transport (``docs/transport.md``) on its fast path —
detector frames are ndarrays, so they ride zero-copy *array frames* (raw
dtype/shape + bytes, no pickle), and the runner batches them through
``produce_many`` (one socket round trip per flush, not per frame). The
producer's backpressure is bounded against what the consumer has
*processed*, not what it has buffered. Swap ``--addr host:port`` for a
reachable interface and the two halves run on different machines unchanged.

Run:  PYTHONPATH=src python examples/remote_ingest.py --frames 96
      PYTHONPATH=src python examples/remote_ingest.py --addr /tmp/broker.sock
"""
import argparse
import multiprocessing as mp
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def produce_frames(address, frames: int, obj_size: int, probe_size: int,
                   max_pending: int) -> None:
    """Producer process: simulate the detector, pump frames over the socket."""
    from repro.apps.ptycho.sim import simulate
    from repro.data import (DetectorSource, IngestConfig, IngestRunner,
                            RemoteBroker)

    problem = simulate(obj_size, probe_size, step=max(8, probe_size // 4))
    # the scan may hold fewer frames than asked for; the detector emits
    # min(frames, problem.num_frames) and the consumer checks against what
    # actually reached the broker
    source = DetectorSource(problem, max_frames=frames, emit_frames=True)
    remote = RemoteBroker(address)
    # The client doubles as the consumer view: lag() is served from the
    # offsets the consumer-side StreamingContext committed on its broker.
    runner = IngestRunner(remote, consumer=remote)
    runner.add(source, IngestConfig(topic="frames", partitions=2,
                                    policy="block", max_pending=max_pending,
                                    poll_batch=16))
    runner.run_inline(timeout=120)
    m = runner.metrics[0]
    print(f"[producer pid={os.getpid()}] pumped "
          f"{m.produced}/{len(source)} frames in "
          f"{m.produce_calls} batched produce calls "
          f"(~{m.produced / max(m.produce_calls, 1):.0f} frames/round trip), "
          f"blocked {m.blocked_s:.2f}s on backpressure, "
          f"max lag seen {m.max_observed_lag}")
    remote.close()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=96)
    ap.add_argument("--obj-size", type=int, default=96)
    ap.add_argument("--probe-size", type=int, default=32)
    ap.add_argument("--batch", type=int, default=16,
                    help="max records per partition per micro-batch")
    ap.add_argument("--max-pending", type=int, default=64,
                    help="producer backpressure bound (records in flight)")
    ap.add_argument("--addr", default="127.0.0.1:0",
                    help='"host:port" for TCP (port 0 = ephemeral) or a '
                         "filesystem path for a Unix domain socket")
    args = ap.parse_args()

    from repro.core import Broker, Context, StreamingContext
    from repro.data import parse_address, serve_broker
    from repro.utils import cpu_only_children

    # consumer side owns the broker; the server publishes it on a socket
    broker = Broker()
    server = serve_broker(broker, parse_address(args.addr))
    print(f"[consumer pid={os.getpid()}] broker served on {server.address}")

    producer = mp.get_context("spawn").Process(
        target=produce_frames,
        args=(server.address, args.frames, args.obj_size, args.probe_size,
              args.max_pending),
        name="detector-producer")
    with cpu_only_children():    # the simulator is data plane: off the chip
        producer.start()

    sc = StreamingContext(Context(), broker, batch_interval=0.05,
                          max_records_per_partition=args.batch)
    # the producer creates the topic over the wire; wait for it to appear
    while "frames" not in broker.topics():
        if not producer.is_alive():
            server.stop()
            raise SystemExit(
                f"producer died before creating the topic "
                f"(exit code {producer.exitcode})")
        time.sleep(0.01)
    sc.subscribe(["frames"])

    stats = {"frames": 0, "photons": 0.0, "peak": 0.0}

    def process(rdd, info):
        frames = rdd.collect()             # (index, magnitude_frame) payloads
        mags = np.stack([f for _, f in frames])
        stats["frames"] += len(frames)
        stats["photons"] += float((mags ** 2).sum())
        stats["peak"] = max(stats["peak"], float(mags.max()))
        print(f"  batch {info.index}: {len(frames)} frames over the wire, "
              f"{stats['frames']} total, lag {sc.lag('frames')}")

    sc.foreach_batch(process)
    t0 = time.time()
    while producer.is_alive() or sc.lag("frames") > 0:
        if sc.run_one_batch() is None:
            time.sleep(0.005)
    producer.join(timeout=30)
    wall = time.time() - t0

    rep = sc.realtime_report()
    print(f"\nconsumed {stats['frames']} frames in {wall:.2f}s "
          f"({stats['frames'] / max(wall, 1e-9):.0f} frames/s over the "
          f"socket); total photons {stats['photons']:.3e}, "
          f"peak magnitude {stats['peak']:.2f}")
    print(f"micro-batches: {rep['batches']}, mean processing "
          f"{rep['mean_processing_s'] * 1e3:.1f} ms, keeps up with "
          f"{sc.batch_interval * 1e3:.0f} ms interval: {rep['keeps_up']}")
    print(f"server stats: {server.requests_served} requests served, "
          f"{server.frames_rejected} frames rejected")
    appended = sum(broker.end_offsets("frames"))
    assert appended > 0 and stats["frames"] == appended, \
        f"lost frames: consumed {stats['frames']} != appended {appended}"
    server.stop()
    if isinstance(server.address, str) and os.path.exists(server.address):
        os.unlink(server.address)
    print("remote ingest complete: every frame crossed the socket exactly "
          "once (block policy; no drops possible)")


if __name__ == "__main__":
    main()
