"""Benchmark harness: one entry per paper table/figure.

  Table I  -> bench_allreduce   (driver-worker vs in-place collectives)
  Table II -> bench_ptycho      (RAAR solver scaling)
  Fig. 16  -> bench_tomo        (ART scaling + TomViz baseline)
  Fig. 7-8 -> bench_streaming   (micro-batch pipeline overhead)
  §V       -> bench_ingest      (source->batch throughput + backpressure)

Prints ``name,us_per_call,derived`` CSV. Roofline numbers for the LM cells
come from the dry-run artifacts (launch/roofline.py), not from here.

``--check`` first runs the project invariant analyzer (``tools/analyze``,
exit 1 on findings — perf numbers from a tree violating the invariants
are not comparable), then only the regression guards: batched ``ingest/produce_many``
must beat per-record ``ingest/remote_transport`` on records/s, the
parallel delivery runtime (``ingest/fanout_parallel``) must beat serial
``fan_out`` by >= 2x wall-clock on the metrics path with one slow sink in
the fan, the durable window state store (``ingest/window_restore``)
must cost <= 1.3x the in-memory store per windowed batch, the metrics
registry (``ingest/obs_overhead``) must tax the instrumented ingest hot
path by <= 1.1x the registry-off run, four group consumers
(``ingest/group_scaleout``) must drain a 4-partition topic at >= 2x the
single-consumer rate, and a live broker replica
(``ingest/replication_overhead``) must tax the durable produce path by
<= 1.3x the unreplicated run, same-host shm frames
(``ingest/shm_fastpath``) must beat 'A'-frame produce by >= 5x on bulk
frames, and int8-codec ingest (``ingest/compressed_ingest``) must beat
raw ingest over a bandwidth-limited link by >= 2x (exit 1 on regression;
``make bench-check`` wires it into CI).
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="regression guards only: batched produce beats "
                         "per-record produce, parallel fan-out beats serial "
                         "fan_out; exit 1 if not")
    ap.add_argument("--check-ratio", type=float, default=3.0,
                    help="minimum produce_many / remote_transport records/s "
                         "ratio for --check (default 3.0)")
    ap.add_argument("--check-fanout-ratio", type=float, default=2.0,
                    help="minimum serial/parallel fan-out wall-clock ratio "
                         "with one slow sink for --check (default 2.0)")
    ap.add_argument("--check-window-overhead", type=float, default=1.3,
                    help="maximum durable/in-memory window state store "
                         "per-batch cost ratio for --check (default 1.3)")
    ap.add_argument("--check-obs-overhead", type=float, default=1.1,
                    help="maximum instrumented/registry-off ingest "
                         "wall-clock ratio for --check (default 1.1)")
    ap.add_argument("--check-group-scaleout", type=float, default=2.0,
                    help="minimum 4-consumer/1-consumer group drain "
                         "throughput ratio for --check (default 2.0)")
    ap.add_argument("--check-replication-overhead", type=float, default=1.3,
                    help="maximum replicated/unreplicated durable produce "
                         "wall-clock ratio for --check (default 1.3)")
    ap.add_argument("--check-shm-ratio", type=float, default=5.0,
                    help="minimum shm/'A'-frame same-host bulk produce "
                         "wall-clock ratio for --check (default 5.0)")
    ap.add_argument("--check-codec-ratio", type=float, default=2.0,
                    help="minimum int8-codec/raw ingest wall-clock ratio "
                         "over a bandwidth-limited link for --check "
                         "(default 2.0)")
    args = ap.parse_args(argv)

    print("name,us_per_call,derived")
    if args.check:
        # guard the guards: perf numbers from a tree that violates the
        # project invariants (docs/static_analysis.md) are not comparable
        from tools.analyze import run as analyze_run
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        findings = analyze_run([os.path.join(repo, "src"),
                                os.path.join(repo, "tests")], root=repo)
        for f in findings:
            print(f"analyze,nan,FAILED: {f.format()}")
        print(f"analyze,0,clean" if not findings
              else f"analyze,nan,{len(findings)} finding(s)")
        if findings:
            return 1
        from benchmarks import bench_ingest
        return 0 if bench_ingest.check(
            min_ratio=args.check_ratio,
            min_fanout_ratio=args.check_fanout_ratio,
            max_window_overhead=args.check_window_overhead,
            max_obs_overhead=args.check_obs_overhead,
            min_group_scaleout=args.check_group_scaleout,
            max_replication_overhead=args.check_replication_overhead,
            min_shm_ratio=args.check_shm_ratio,
            min_codec_ratio=args.check_codec_ratio) else 1

    from benchmarks import (bench_allreduce, bench_ingest, bench_ptycho,
                            bench_streaming, bench_tomo)
    failed = 0
    for mod in (bench_allreduce, bench_ptycho, bench_tomo, bench_streaming,
                bench_ingest):
        try:
            mod.run()
        except Exception:
            failed += 1
            print(f"{mod.__name__},nan,FAILED: "
                  + traceback.format_exc().strip().splitlines()[-1])
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
