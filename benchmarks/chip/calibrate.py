#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 1,2,3,... --control-seeds 4,5,6 [--seconds 0] [--out FILE]

For each of ``--seeds`` it runs the cell once in this process, as
``run.py`` does (``--seconds 0`` measures one scan), and records
every number the check compared. For each of ``--control-seeds`` it puts
the control in the program's place: the plain reference computed in
bfloat16, the precision below the configuration's float32, compared with
the reference by the same numbers. One JSON line per reading goes to
standard output and to ``--out``. The limits in the configuration files
lie between the largest program reading and the smallest control reading.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def control_readings(workload: str, seed: int) -> dict:
    """The control's numbers for one seed: the reference in bfloat16 against
    the reference, on the sample the check would draw."""
    import ml_dtypes
    import numpy as np
    from chipbench import harness, spec
    cell = spec.find_cell(workload)
    mod = harness.load_app(cell.config)
    app = mod.App(cell.config, cell.traffic, seed,
                  os.path.join(harness.OUT_ROOT, workload + ".control"))
    app.make_inputs()
    rng = np.random.default_rng(seed)
    o = int(rng.integers(app.objects))
    checks = app.compare(app.control_outputs(o, ml_dtypes.bfloat16), o)
    return {c.name: c.value for c in checks}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from chipbench import harness
    out = open(args.out, "a") if args.out else None

    def emit(row: dict) -> None:
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for s in filter(None, args.seeds.split(",")):
        t0 = time.monotonic()
        r = harness.run_cell(args.workload, int(s), args.seconds, False,
                             t_start=t0)
        emit({"kind": "program", "seed": int(s), "correct": r["correct"],
              "checks": {k: v["value"] for k, v in r["checks"].items()},
              "metrics": {k: v["value"] for k, v in r["metrics"].items()},
              "seconds": time.monotonic() - t0})
    for s in filter(None, args.control_seeds.split(",")):
        t0 = time.monotonic()
        emit({"kind": "control", "seed": int(s),
              "checks": control_readings(args.workload, int(s)),
              "seconds": time.monotonic() - t0})
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
