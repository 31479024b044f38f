"""Tomography pipeline (paper §IV, Figs. 11-16): stream -> partition -> ART ->
gather -> render, on the data subsystem.

The paper's four steps, streamed instead of preloaded:
  1. the TEM tilt series arrives as slice records through a
     ProjectionSource (paper: "load the TEM dataset into RDD format");
  2. each micro-batch groups neighbouring slices (paper step 2 —
     repartition by proximity; slices stream in scan order);
  3. every batch runs the ART sweep (Pallas kernel on a TPU) partition-
     parallel — the scheduler retries failures and re-executes stragglers;
  4. sub-volumes land in an idempotent NpzDirectorySink (checkpoint store),
     assemble, and render to PNG/NPY (the ParaView/ParaViewWeb stage,
     stubbed per DESIGN.md).

Run:  PYTHONPATH=src python examples/tomo_pipeline.py --nray 64 --nslice 32
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.apps.tomo.render import render_volume
from repro.apps.tomo.solver import (SliceReconstructor, TomoConfig,
                                    reconstruct_batch, residual,
                                    simulate_tilt_series)
from repro.core import Broker, Context, NearRealTimePipeline, PipelineConfig
from repro.core.rdd import TaskScheduler
from repro.data import MetricsSink, NpzDirectorySink, ProjectionSource
from repro.utils import enable_compile_cache


def main(argv: list[str] | None = None) -> dict:
    """Run the pipeline; returns what it measured."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--nray", type=int, default=64)
    ap.add_argument("--nslice", type=int, default=32)
    ap.add_argument("--angles", type=int, default=25)
    ap.add_argument("--iterations", type=int, default=2)
    ap.add_argument("--partitions", type=int, default=4)
    ap.add_argument("--slice-interval", type=float, default=0.0,
                    help="seconds between streamed slices (acquisition rate)")
    ap.add_argument("--obs-port", type=int, default=None,
                    help="serve the observability endpoint on this port "
                         "while the pipeline runs (0 = ephemeral port)")
    ap.add_argument("--out", default="out")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = TomoConfig(
        nray=args.nray,
        angles=tuple(np.linspace(-75, 75, args.angles).tolist()),
        iterations=args.iterations)

    # step 1: the tilt series streams in as (slice_index, sinogram_row)
    vol_true, sino = simulate_tilt_series(cfg, args.nslice)
    source = ProjectionSource(sino, interval=args.slice_interval)
    # per-run-shape directory: the gather below reads every key on disk, so
    # sub-volumes from a differently-shaped run must not share the store
    # (same-shape reruns resume idempotently, which is the point)
    run_tag = f"{args.nslice}x{args.nray}x{args.angles}x{args.iterations}"
    sink = NpzDirectorySink(os.path.join(args.out,
                                         f"tomo_subvolumes_{run_tag}"))
    metrics = MetricsSink()
    ctx = Context(scheduler=TaskScheduler(num_executors=args.partitions,
                                          speculation=True))
    batch_slices = max(1, args.nslice // args.partitions)

    # steps 2+3 per micro-batch: repartition neighbouring slices, ART sweep
    # against the system matrix the operator keeps on the device
    operator = SliceReconstructor(cfg)

    def process(rdd, info, bridge):
        return reconstruct_batch(rdd, operator, args.partitions)

    pipeline = NearRealTimePipeline(
        Broker(),
        PipelineConfig(batch_interval=0.02,
                       max_records_per_partition=batch_slices),
        process,
        context=ctx,
        sinks=[sink, metrics])
    pipeline.subscribe_source(source, topic="tilt-series")
    obs = None
    if args.obs_port is not None:
        obs = pipeline.serve_observability(("127.0.0.1", args.obs_port))
        print(f"observability endpoint: {obs.url}")

    t0 = time.time()
    pipeline.run_until_drained()
    dt = time.time() - t0
    if obs is not None:
        spans = pipeline.streaming.traces.last()
        stages = pipeline.streaming.traces.stage_totals()
        top = max(stages, key=stages.get) if stages else "-"
        print(f"observability: {len(spans)} batch spans at {obs.url}/traces; "
              f"slowest stage: {top} ({stages.get(top, 0.0):.3f}s)")
        pipeline.close()       # stops the endpoint with the lanes

    # step 4: gather sub-volumes from the checkpoint store + render
    recon = np.zeros((args.nslice, args.nray, args.nray), np.float32)
    for key in sink.keys_on_disk():
        with np.load(sink.path_for(key)) as z:
            recon[z["idx"]] = z["block"]
    r = residual(recon, sino, cfg)
    err = np.linalg.norm(recon - vol_true) / np.linalg.norm(vol_true)
    rep = metrics.report()
    print(f"ART: {args.nslice} slices x {args.nray}^2, "
          f"{args.angles} angles, {args.iterations} sweeps "
          f"on {args.partitions} partitions: {dt:.1f}s "
          f"({rep['batches']} micro-batches, "
          f"{rep['throughput_rec_per_s']:.1f} slices/s)")
    print(f"sinogram residual {r:.3f}; volume rel. error {err:.3f}")
    print(f"scheduler metrics: {ctx.scheduler.metrics}; system matrix "
          f"placed on the device {operator.placements}x "
          f"({operator.placed_bytes / 1e6:.1f} MB)")
    print(f"sub-volume artifacts: {sink.keys_on_disk()}")
    paths = render_volume(recon, args.out)
    print("artifacts:", paths)
    return {"residual": r, "volume_error": float(err),
            "batches": rep["batches"], "slices": args.nslice, "seconds": dt,
            "artifact_keys": sink.keys_on_disk(), "renders": paths,
            "system_placements": operator.placements}


if __name__ == "__main__":
    main()
