"""Runs one cell once: set-up, the measured window, the reading of the
trace, the check of what the window published, and the result line."""
from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from chipbench import device, spec, xplane
from chipbench.compiles import CompileCounter

OUT_ROOT = os.path.join(spec.CHECKOUT, ".chipbench")
CACHE_DIR = os.path.join(spec.CHECKOUT, ".jax_cache")


@dataclass
class Check:
    """One number the check compares, with its limit: correct while
    ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclass
class Window:
    t0: float               # time.perf_counter()
    t1: float
    wall0: float            # time.time()
    wall1: float

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclass
class Run:
    """What a per-layer metric's reader gets."""
    workload: str
    config: dict[str, Any]
    traffic: dict[str, Any]
    window: Window
    compiles_in_window: int
    peaks: dict[str, Any]
    trace: xplane.Reduction | None
    facts: dict[str, Any] = field(default_factory=dict)


def load_app(config: dict[str, Any]):
    return importlib.import_module(f"chipbench.apps.{config['app']}")


def enable_cache(jax: Any) -> None:
    """JAX's persistent compilation cache at the fixed path
    ``<checkout>/.jax_cache``, so only a checkout's first run compiles."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _start_trace(jax: Any, log_dir: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # the Python tracer costs every call
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float) -> dict[str, Any]:
    """Run ``workload`` once and return its result line.

    ``t_start`` is ``time.monotonic()`` at process start; set-up runs from
    there to the first timed operation.
    """
    import jax

    cell = spec.find_cell(workload)
    device.require_chips(jax, cell.chips)
    enable_cache(jax)
    config, traffic = cell.config, cell.traffic
    out_dir = os.path.join(OUT_ROOT, workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    counter = CompileCounter(jax)
    app = load_app(config).App(config, traffic, seed, out_dir)
    app.setup()

    compiles0 = counter.programs
    trace_dir = os.path.join(out_dir, "trace")
    if trace:
        _start_trace(jax, trace_dir)
    setup_s = time.monotonic() - t_start
    wall0, t0 = time.time(), time.perf_counter()
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        while True:
            app.run_unit()
            if time.perf_counter() - t0 >= seconds:
                break
    window = Window(t0=t0, t1=time.perf_counter(), wall0=wall0,
                    wall1=time.time())
    reduction = None
    if trace:
        jax.profiler.stop_trace()
    compiles = counter.programs - compiles0
    app.close()
    dev = device.describe(jax, cell.chips)
    if trace:
        reduction = xplane.reduce_trace(xplane.find_trace(trace_dir))
        dev["busy_s"] = reduction.busy_s
        dev["window_s"] = reduction.window_s
    facts = app.facts(window)
    e2e = app.end_to_end(window) if not trace else {}
    app.release()

    checks = app.check(np.random.default_rng(seed))
    attempted, failed = app.attempted_failed(window)
    units_of = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics: dict[str, dict[str, Any]] = {}
    if trace:
        run = Run(workload, config, traffic, window, compiles,
                  device.peaks_for(dev["kind"]), reduction, facts)
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": units_of[m["name"]]}
    result: dict[str, Any] = {
        "correct": all(c.ok for c in checks) and failed == 0,
        "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": dev,
    }
    if trace:
        result["breakdown"] = {
            "device_ops": app.breakdown_ops(reduction),
            "idle_gaps": reduction.idle_gaps(10),
        }
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def report(result: dict[str, Any]) -> None:
    """Each compared number beside its limit as the last lines on standard
    error, then the result as the last line of standard output."""
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
