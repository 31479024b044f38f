"""``metrics/art_passes_per_slice.py`` on a trace recorded on the CPU: the
operator's ``repro.tomo.sweep`` spans, one per call, give the passes of the
system matrix per slice; a window without them, and a program without the
span, give nothing."""
import math
import os
import shutil
from types import SimpleNamespace

import jax
import numpy as np

from chipbench import harness, program_trace, spec, xplane
from repro.apps.tomo.solver import SliceReconstructor, TomoConfig

HERE = os.path.dirname(os.path.abspath(__file__))
EXPLORE = os.path.join(HERE, "testdata", "explore.xplane.pb")
WORKLOAD = "tomo-art256.volume"
READ = spec.metric_reader("art_passes_per_slice")


def _run(tmp_path, monkeypatch, trace, window):
    monkeypatch.setattr(harness, "OUT_ROOT", str(tmp_path / "out"))
    dest = tmp_path / "out" / WORKLOAD / "trace" / "plugins" / "profile"
    dest.mkdir(parents=True)
    shutil.copy(trace, dest / "run.xplane.pb")
    return SimpleNamespace(workload=WORKLOAD,
                           trace=SimpleNamespace(window=window))


def test_passes_per_slice_from_the_operators_spans(tmp_path, monkeypatch):
    cfg = TomoConfig(nray=16, angles=(-60.0, 0.0, 60.0), iterations=2)
    operator = SliceReconstructor(cfg)
    nrow = len(cfg.angles) * cfg.nray
    jax.profiler.start_trace(str(tmp_path / "rec"))
    try:
        for k in (2, 20):           # one slice block, then two
            operator(np.ones((k, nrow), np.float32))
    finally:
        jax.profiler.stop_trace()
    trace = xplane.find_trace(str(tmp_path / "rec"))
    spans = program_trace.read_file(trace).spans_named("repro.tomo.sweep")
    assert len(spans) == 2 and operator.matrix_passes == 2 + 4
    run = _run(tmp_path, monkeypatch, trace, (0, math.inf))
    assert READ(run) == (2 + 4) / (2 + 20)
    run.trace.window = (spans[-1].end_ns + 1, math.inf)
    assert READ(run) is None


def test_nothing_for_a_program_without_the_span(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch, EXPLORE, (0, math.inf))
    assert READ(run) is None
    run.trace = None                            # an untraced run
    assert READ(run) is None
