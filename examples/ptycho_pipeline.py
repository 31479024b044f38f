"""End-to-end near-real-time ptychography pipeline (paper §III, Figs. 7-10).

The full Spark-MPI loop, on the data subsystem:
  DetectorSource (frame simulator at the acquisition rate)
     --> broker topic --> StreamingContext micro-batches
     --> RAAR reconstruction on accumulated frames (the "MPI application":
         modulus + overlap + combine, Pallas kernels; partial sums psum
         across the worker mesh when world > 1)
     --> sinks: NpzDirectorySink artifacts + MetricsSink latency accounting
         + final phase image (Fig. 10)

No hand-rolled producer thread and no direct ``broker.produce`` calls: the
pipeline pulls the detector through ``subscribe_source`` and pushes results
through idempotent keyed sinks.

The paper's near-real-time criterion: 512 frames arrive in ~25 s; the
pipeline reports whether reconstruction kept pace.

Sinks ride the parallel delivery runtime: the NPZ artifact store gets its
own lane (retry x2, bounded queue) so a slow disk cannot stall the batch
loop, and per-lane depth/latency counters print next to the MetricsSink
report. With ``--elastic`` the detector is pumped by a threaded IngestRunner
and a LagPolicy watches its backpressure lag, growing an ElasticController's
worker set when reconstruction falls behind the acquisition rate and
handing the pipeline the re-formed mesh. This demos the control loop
(signal -> policy -> controller -> new mesh) on virtual devices; the RAAR
step itself stays single-device, so scale events change the mesh, not the
reconstruction speed.

With ``--restart`` the example demos the restart-safe windowed state path
instead: detector frames land in a durable-log broker, reconstruction runs
per *window* of frames (``NearRealTimePipeline(window=..., window_state=
DurableStateStore(...))``), and the consumer is SIGKILLed mid-window. The
resumed run restores the open window atomically with the consumed offsets
and must produce the exact per-window reconstruction set an uncrashed run
produces — no frame lost off the open window, none duplicated.

Run:  PYTHONPATH=src python examples/ptycho_pipeline.py
(the defaults are the paper's Table II size: 512 frames of 64² streamed
over a 256² object; a few seconds on one TPU v5e, minutes on a CPU;
--fast shrinks everything)
"""
import argparse
import json
import multiprocessing
import os
import shutil
import signal
import sys
import time

# the elastic demo grows the worker set: give XLA virtual devices to grow
# into (must be set before jax initializes)
if "--elastic" in sys.argv and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.apps.ptycho.sim import scan_grid, simulate
from repro.apps.ptycho.solver import (SolverConfig, init_waves, raar_step,
                                      reconstruction_quality)
from repro.apps.tomo.render import render_phase
from repro.core import (Broker, ElasticController, LagPolicy,
                        NearRealTimePipeline, PipelineConfig)
from repro.data import (DetectorSource, DurableLogFactory, DurableStateStore,
                        IngestConfig, IngestRunner, MetricsSink,
                        NpzDirectorySink, SinkPolicy, WindowSpec)
from repro.utils import enable_compile_cache


def _restart_consume(root: str, sim_args: tuple, n_frames: int, window: int,
                     batch: int, iters: int, sleep_s: float = 0.0) -> None:
    """Consumer half of the ``--restart`` demo: windowed RAAR over a durable
    broker with restart-safe window state. Run once in a child (killed
    mid-window), then again in-process to resume from the checkpoint."""
    problem = simulate(*sim_args)
    positions = jnp.asarray(problem.positions)
    probe = jnp.asarray(problem.probe_true)
    obj_shape = problem.object_true.shape
    cfg = SolverConfig(beta=0.75, iterations=iters)

    factory = DurableLogFactory(os.path.join(root, "wal"))
    broker = Broker(log_factory=factory)
    factory.restore(broker)                # reopen the on-disk frame log
    sink = NpzDirectorySink(os.path.join(root, "windows"))

    def process(frame_ids, winfo, bridge):
        ids = np.asarray(sorted(frame_ids))
        mags = problem.magnitudes[ids]
        psi, pr = init_waves(mags, probe), probe
        for it in range(iters):
            psi, obj, pr, err = raar_step(psi, mags, positions[ids], pr,
                                          obj_shape, cfg, it)
        tag = "partial-" if winfo.partial else ""
        print(f"  window {tag}{winfo.index}: frames "
              f"[{ids[0]}..{ids[-1]}], fourier err {float(err):.4f}")
        return (f"win-{tag}{winfo.index:04d}",
                {"frames": ids, "fourier_err": np.float32(err)})

    pipeline = NearRealTimePipeline(
        broker,
        PipelineConfig(topics=("frames",), batch_interval=0.01,
                       max_records_per_partition=batch,
                       checkpoint_path=os.path.join(root, "ckpt.json")),
        process,
        window=WindowSpec(size=window),
        window_state=DurableStateStore(os.path.join(root, "wstate")),
        sinks=[sink])
    if sleep_s:                            # slow the batch loop so the
        pipeline.streaming.add_sink(       # parent can catch it mid-window
            lambda info: time.sleep(sleep_s))
    pipeline.run_until_drained(producer_done=lambda: True, idle_timeout=0.2)
    pipeline.flush_windows()     # partial window -> keyed sinks, THEN ckpt
    pipeline.close()


def run_restart_demo(args) -> None:
    root = os.path.join(args.out, "ptycho-restart")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    sim_args = (args.obj_size, args.probe_size, args.scan_step)
    # the parent stays off JAX (the scan grid is numpy): the consumer child
    # below needs the accelerator, which belongs to one process at a time
    n_frames = min(args.frames, len(scan_grid(*sim_args)))
    window, batch = args.batch_frames, max(1, args.batch_frames // 3)
    print(f"restart demo: {n_frames} frames -> durable WAL, window {window}, "
          f"{batch} frames/batch")

    # produce the acquisition into the durable log (survives the kill): the
    # DetectorSource's index records, since the consumer re-simulates the
    # measurements itself
    factory = DurableLogFactory(os.path.join(root, "wal"))
    producer = Broker(log_factory=factory)
    producer.create_topic("frames", 1)
    producer.produce_many(
        "frames", [(f"frame-{i:06d}".encode(), i) for i in range(n_frames)],
        partition=0)

    consume = (root, sim_args, n_frames, window, batch, args.iters_per_batch)
    proc = multiprocessing.get_context("spawn").Process(
        target=_restart_consume, args=consume + (0.3,), daemon=True)
    proc.start()
    ckpt = os.path.join(root, "ckpt.json")
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        if not proc.is_alive():
            raise SystemExit("consumer drained before it could be killed — "
                             "raise --frames")
        try:
            with open(ckpt) as f:
                consumed = sum(sum(v)
                               for v in json.load(f)["offsets"].values())
        except (OSError, ValueError, KeyError):
            consumed = 0
        if consumed > window and consumed % window != 0:
            os.kill(proc.pid, signal.SIGKILL)
            print(f"SIGKILL at {consumed} frames consumed "
                  f"({consumed % window} accumulated in the open window)")
            break
        time.sleep(0.01)
    else:
        proc.kill()
        raise SystemExit("never caught the consumer mid-window")
    proc.join(timeout=30)
    before = set(NpzDirectorySink(os.path.join(root, "windows"))
                 .keys_on_disk())
    print(f"windows on disk at crash: {sorted(before)}")

    print("resuming from the (offsets, window state) checkpoint ...")
    _restart_consume(*consume)

    sink = NpzDirectorySink(os.path.join(root, "windows"))
    got = {}
    for key in sink.keys_on_disk():
        with np.load(sink.path_for(key)) as z:
            got[key] = z["frames"].tolist()
    expect = {f"win-{k:04d}": list(range(k * window, (k + 1) * window))
              for k in range(n_frames // window)}
    if n_frames % window:
        k = n_frames // window
        expect[f"win-partial-{k:04d}"] = list(range(k * window, n_frames))
    if got != expect:
        raise SystemExit(f"MISMATCH after restart:\n  got {got}\n"
                         f"  want {expect}")
    print(f"restart OK: {len(got)} windows, identical reconstruction set "
          f"(no frame lost off the open window, none duplicated)")


def main(argv: list[str] | None = None) -> dict | None:
    """Run the pipeline; returns what it measured (None for --restart)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=512)
    ap.add_argument("--obj-size", type=int, default=256)
    ap.add_argument("--probe-size", type=int, default=64)
    ap.add_argument("--scan-step", type=int, default=8,
                    help="scan raster step in pixels (8 gives 625 positions "
                         "at the default sizes, enough for --frames 512)")
    ap.add_argument("--frame-interval", type=float, default=0.0,
                    help="seconds between produced frames (paper: 0.05)")
    ap.add_argument("--batch-frames", type=int, default=64)
    ap.add_argument("--iters-per-batch", type=int, default=6)
    ap.add_argument("--final-iters", type=int, default=60)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--elastic", action="store_true",
                    help="threaded ingest + LagPolicy-driven elastic scaling")
    ap.add_argument("--restart", action="store_true",
                    help="SIGKILL mid-window + resume: restart-safe windowed "
                         "state demo (durable WAL + DurableStateStore)")
    ap.add_argument("--obs-port", type=int, default=None,
                    help="serve the observability endpoint (/metrics, "
                         "/metrics.json, /traces, /health) on this port "
                         "while the pipeline runs (0 = ephemeral port)")
    ap.add_argument("--out", default="out")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.fast or args.restart:
        args.frames, args.obj_size, args.probe_size = 81, 96, 32
        args.scan_step, args.batch_frames = 8, 27
        args.final_iters, args.iters_per_batch = 30, 4
    if args.restart:
        run_restart_demo(args)
        return None

    # ground truth + measurements (the detector)
    problem = simulate(args.obj_size, args.probe_size, args.scan_step)
    n_frames = min(args.frames, problem.num_frames)
    print(f"scan: {problem.num_frames} frames of "
          f"{problem.frame_shape}; streaming {n_frames} of {args.frames} "
          f"requested")
    if n_frames < args.frames:
        print(f"WARNING: the scan holds only {problem.num_frames} positions; "
              f"lower --scan-step to stream all {args.frames} frames")

    source = DetectorSource(problem, max_frames=n_frames,
                            frame_interval=args.frame_interval)
    artifact_sink = NpzDirectorySink(os.path.join(args.out, "ptycho"))
    metrics = MetricsSink()

    # reconstruction state (solver warm-starts across micro-batches)
    cfg = SolverConfig(beta=0.75, iterations=args.final_iters)
    positions_all = jnp.asarray(problem.positions)
    mags_all = problem.magnitudes
    probe = jnp.asarray(problem.probe_true)      # known probe mode to start
    state = {"probe": probe, "n_seen": 0, "psi": None, "obj": None,
             "iteration": 0, "errs": []}
    obj_shape = problem.object_true.shape
    step = jax.jit(lambda psi, mag, pos, probe, it: raar_step(
        psi, mag, pos, probe, obj_shape, cfg, it))

    def process(rdd, info, bridge):
        ids = sorted(rdd.collect())
        if not ids:
            return None
        n_new = state["n_seen"] + len(ids)
        mags = mags_all[:n_new]
        pos = positions_all[:n_new]
        if state["psi"] is None:
            psi = init_waves(mags, state["probe"])
        else:
            psi = jnp.concatenate(
                [state["psi"], init_waves(mags[state["n_seen"]:],
                                          state["probe"])])
        for _ in range(args.iters_per_batch):
            psi, obj, probe_new, err = step(psi, mags, pos, state["probe"],
                                            state["iteration"])
            state["probe"] = probe_new
            state["iteration"] += 1
        state.update(psi=psi, obj=obj, n_seen=n_new)
        state["errs"].append(float(err))
        # keyed result -> idempotent sink (replays overwrite, not duplicate)
        return [(f"batch-{info.index:06d}",
                 {"fourier_err": np.float32(err),
                  "frames_seen": np.int32(n_new)})]

    broker = Broker()
    if args.elastic:
        broker.create_topic("frames", 2)
    pipeline = NearRealTimePipeline(
        broker,
        PipelineConfig(topics=("frames",) if args.elastic else (),
                       batch_interval=0.05,
                       max_records_per_partition=args.batch_frames // 2,
                       source_partitions=2),
        process,
        # artifact store on its own delivery lane: a slow disk can no longer
        # stall the batch loop, and transient write errors retry twice
        sinks=[metrics, (artifact_sink, SinkPolicy.retry(2, queue_depth=32))])

    def print_batch(info):
        # a serial sink: it runs once the stream has stamped the batch's
        # processing time, which the batch function itself cannot see yet
        if info.result is not None:
            print(f"  batch {info.index}: {state['n_seen']}/{n_frames} "
                  f"frames, fourier err {state['errs'][-1]:.4f}, "
                  f"proc {info.processing_time:.2f}s")

    pipeline.streaming.add_sink(print_batch)

    runner = controller = policy = None
    if args.elastic:
        # threaded ingest with block backpressure against consumed offsets;
        # LagPolicy grows the worker set when reconstruction falls behind
        controller = ElasticController(initial_workers=1)
        policy = LagPolicy(scale_up_lag=args.batch_frames // 2,
                           scale_down_lag=max(1, args.batch_frames // 8),
                           sustain=2, cooldown=0.5)
        runner = IngestRunner(broker, consumer=pipeline.streaming)
        runner.add(source, IngestConfig(
            topic="frames", partitions=2, policy="block",
            poll_batch=args.batch_frames,
            max_pending=4 * args.batch_frames))

        def drive_elastic(info):
            # on a scale event, hand the pipeline the re-formed mesh. The
            # RAAR step here stays single-device (process() ignores the
            # bridge), so this demo exercises the CONTROL loop — signal ->
            # policy -> controller -> new mesh — not parallel reconstruction.
            if policy.drive(controller, runner) != 0:
                pipeline.bridge = controller.bridge()

        pipeline.streaming.add_sink(drive_elastic)
        print(f"elastic: starting on {controller.world}/"
              f"{controller.max_workers} workers")
        runner.start()
    else:
        pipeline.subscribe_source(source, topic="frames")

    obs = None
    if args.obs_port is not None:
        # live while the stream runs: scrape /metrics mid-run, or watch
        # /health flip to degraded when reconstruction falls behind
        obs = pipeline.serve_observability(("127.0.0.1", args.obs_port),
                                           lag_policy=policy)
        print(f"observability endpoint: {obs.url}")

    t0 = time.time()
    report = pipeline.run_until_drained(
        producer_done=(lambda: runner.done) if runner else None)
    if runner is not None:
        runner.stop()
    obs_snap = obs_spans = None
    if obs is not None:        # fetch THROUGH the endpoint before close()
        import urllib.request  # stops it — this is the end-to-end demo
        with urllib.request.urlopen(obs.url + "/metrics.json") as r:
            obs_snap = json.load(r)
        with urllib.request.urlopen(obs.url + "/traces?last=1024") as r:
            obs_spans = json.load(r)["spans"]
    pipeline.close()           # drain the artifact lane: all batches on disk
    stream_time = time.time() - t0

    # refinement to convergence (the offline tail, paper Table II setup)
    psi, pos, mags = state["psi"], positions_all[:n_frames], \
        mags_all[:n_frames]
    probe = state["probe"]
    for it in range(args.final_iters):
        psi, obj, probe, err = step(psi, mags, pos, probe,
                                    state["iteration"] + it)
    total = time.time() - t0
    q = reconstruction_quality(obj, problem.object_true,
                               margin=args.probe_size // 2)
    # overwrite: the final object must track THIS run, not a previous one
    artifact_sink.write_batch([
        ("object-final", {"obj": np.asarray(obj),
                          "fourier_err": np.float32(err)})], overwrite=True)
    acq = 0.05 * n_frames
    rep = metrics.report()
    print(f"\nstreamed {report.records} frames of {args.frames} requested")
    print(f"streaming phase: {stream_time:.1f}s for {report.records} frames"
          f" ({rep['mean_latency_s']:.2f}s/batch, "
          f"{rep['throughput_rec_per_s']:.0f} rec/s)")
    print(f"total (incl. {args.final_iters} refinement iters): {total:.1f}s "
          f"vs paper acquisition window {acq:.0f}s "
          f"-> near-real-time: {total < acq}")
    for name, lane in pipeline.delivery_report().items():
        print(f"sink lane {name}: delivered {lane['delivered']}, "
              f"failed {lane['failed']}, retries {lane['retries']}, "
              f"max depth {lane['max_depth']}, "
              f"mean latency {lane.get('mean_latency_s', 0.0):.4f}s")
    if obs_spans:
        # the trace spans answer "which stage ate the time", per batch epoch
        stages: dict = {}
        for s in obs_spans:
            for k, v in s["stages"].items():
                stages[k] = stages.get(k, 0.0) + v
        span_total = max(sum(s["total_s"] for s in obs_spans), 1e-9)
        batch_vals = {m["name"]: m["value"] for m in obs_snap["metrics"]
                      if not m["labels"]}
        print(f"\nobservability: {len(obs_spans)} batch spans (epochs "
              f"{obs_spans[0]['epoch']}..{obs_spans[-1]['epoch']}), "
              f"{batch_vals.get('stream_records_total', 0):.0f} records via "
              f"{batch_vals.get('stream_batches_total', 0):.0f} batches; "
              f"per-stage time:")
        for k, v in sorted(stages.items(), key=lambda kv: -kv[1]):
            print(f"  {k:16s} {v:8.3f}s  ({100 * v / span_total:5.1f}%)")
    if args.elastic:
        shed = sum(m.dropped + m.sampled_out for m in runner.metrics)
        peak = max((o.lag for o in policy.history), default=0)
        print(f"elastic: peak consumer lag {peak} records, {shed} shed; "
              f"world {controller.world}/{controller.max_workers} after "
              f"{len(controller.events)} scale event(s)")
        for ev in controller.events:
            print(f"  gen {ev.generation}: {ev.reason} (world {ev.world})")
    print(f"final fourier error {float(err):.4f}, "
          f"phase correlation vs truth {q:.3f}")
    print(f"sink artifacts: {len(artifact_sink.keys_on_disk())} npz files "
          f"in {artifact_sink.directory}")
    paths = render_phase(np.asarray(obj), args.out)
    print("artifacts:", paths)
    return {"frames_requested": args.frames, "frames_streamed": report.records,
            "batch_errors": state["errs"], "final_error": float(err),
            "phase_correlation": q, "stream_s": stream_time, "total_s": total,
            "artifact_dir": artifact_sink.directory,
            "artifact_keys": artifact_sink.keys_on_disk(), "renders": paths}


if __name__ == "__main__":
    main()
