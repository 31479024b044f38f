"""``chipbench.program_trace`` and the seven readers built on it, on two
small traces recorded on one TPU v5e: ``explore.xplane.pb`` (see
``test_chipbench_trace.py``; its RAAR steps predate the phase scopes, as
the parent of a change does) and ``raar_phases.xplane.pb``: three RAAR
steps with the scopes, 64 frames of 32² on a 128² object, inside one
``bench.raar`` span (``testdata/record_phases.py``)."""
import json
import os
import shutil
from types import SimpleNamespace

import pytest

from chipbench import harness, program_trace, spec, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
EXPLORE = os.path.join(HERE, "testdata", "explore.xplane.pb")
PHASES = os.path.join(HERE, "testdata", "raar_phases.xplane.pb")
SIX = ("far_field", "modulus", "object_solve", "probe_solve", "exit_waves",
       "combine")
NEW = [f"raar_{p}_ms" for p in SIX] + ["batch_idle_ms"]


def test_reads_every_op_event_of_chip0():
    prog = program_trace.read_file(EXPLORE)
    red = xplane.reduce_trace(EXPLORE)
    assert len(prog.ops) == len(red.op_events) > 100
    mine = sorted((xplane.op_name(o.name), o.start_ns, o.end_ns)
                  for o in prog.ops)
    for (n1, s1, e1), (n2, s2, e2) in zip(mine, sorted(red.op_events)):
        assert n1 == n2 and abs(s1 - s2) < 1 and abs(e1 - e2) < 2
    # the compiler kept an op_name, so the trace a tf_op, on every kernel
    kernels = [o for o in prog.ops if xplane.op_name(o.name) in
               ("modulus_project", "overlap_products", "raar_combine")]
    assert len(kernels) == 12
    for o in kernels:
        assert o.scope.endswith("/pallas_call:"), o.scope
        assert o.phase is None                 # no RAAR scopes back then


def test_scope_paths_agree_with_xprof():
    """xprof's own reading of the same file: its trace viewer's ``tf_op``
    on each of chip 0's ``XLA Ops`` events, in order."""
    convert = pytest.importorskip("xprof.convert.raw_to_tool_data")
    data, _ = convert.xspace_to_tool_data([EXPLORE], "trace_viewer",
                                          {"use_saved_result": False})
    events = json.loads(data)["traceEvents"]
    meta = [e for e in events if e.get("ph") == "M"]
    pid = next(e["pid"] for e in meta if e["name"] == "process_name"
               and e["args"]["name"] == "/device:TPU:0")
    tid = next(e["tid"] for e in meta if e["name"] == "thread_name"
               and e["pid"] == pid and e["args"]["name"] == "XLA Ops")
    theirs = sorted((e for e in events if e.get("ph") == "X"
                     and e["pid"] == pid and e["tid"] == tid),
                    key=lambda e: e["ts"])
    ours = program_trace.read_file(EXPLORE).ops
    assert len(theirs) == len(ours)
    for e, op in zip(theirs, ours):
        assert e["args"]["long_name"] == op.name
        assert e["args"].get("tf_op", "") == op.scope


def test_an_op_with_no_scope_takes_its_operands_phase():
    info = program_trace._OpInfo
    infos = {
        1: info("%mul.1 = f32[4] multiply(f32[4] %p, f32[4] %q)",
                "jit(f)/raar/object_solve/mul:", 7),
        2: info("%reshape.2 = f32[4] reshape(f32[4] %mul.1)", "", 7),
        3: info("%fusion.3 = (f32[2], f32[2]) fusion(f32[4] %reshape.2, "
                "f32[4] %exp.4, f32[4] %reshape.2), calls=%fc.1", "", 7),
        4: info("%exp.4 = f32[4] exponential(f32[4] %x)",
                "jit(f)/raar/far_field/exp:", 7),
        5: info("%copy.5 = f32[4] copy(f32[4] %gte.9)", "", 7),
        6: info("%copy.6 = f32[4] copy(f32[4] %mul.1)", "", 8),
        7: info("%copy.7 = f32[4] copy(f32[4] %copy.8)", "", 7),
        8: info("%copy.8 = f32[4] copy(f32[4] %copy.7)", "", 7),
        9: info("%copy.9 = f32[4] copy(f32[4] %mul.1)", "psi:", 7),
    }
    assert program_trace._phases(infos) == {
        1: "object_solve", 2: "object_solve",
        3: "object_solve",      # two operands of three
        4: "far_field",
        5: None,                # its operand never ran as an op
        6: None,                # %mul.1 of another program
        7: None, 8: None,       # a cycle ends
        9: None,                # a scope of its own, not a RAAR phase
    }


def _run(tmp_path, monkeypatch, trace, span, iterations):
    """A traced run of the ptychography cell whose trace is ``trace``, with
    the recorded ``span`` standing for the refinement and for a batch."""
    monkeypatch.setattr(harness, "OUT_ROOT", str(tmp_path))
    dest = tmp_path / "ptycho-t2.scan" / "trace" / "plugins" / "profile"
    dest.mkdir(parents=True)
    shutil.copy(trace, dest / "run.xplane.pb")
    red = xplane.reduce_trace(trace)
    s = red.spans_named(span)[0]
    red.spans = [xplane.Span("bench.ptycho.refine", s.start_ns, s.end_ns)]
    return SimpleNamespace(workload="ptycho-t2.scan", trace=red,
                           facts={"refine_iterations": iterations})


def test_readers_give_nothing_for_a_program_without_scopes_or_spans(
        tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch, EXPLORE, "bench.raar", 3)
    for name in NEW:
        assert spec.metric_reader(name)(run) is None, name
    run.trace = None                            # an untraced run
    for name in NEW:
        assert spec.metric_reader(name)(run) is None, name


def test_six_phases_cover_the_step_on_the_chip(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch, PHASES, "bench.raar", 3)
    s = run.trace.spans[0]
    step_ms = 1e3 * run.trace.busy_between(s.start_ns, s.end_ns) / 3
    phases = {p: spec.metric_reader(f"raar_{p}_ms")(run) for p in SIX}
    assert all(v > 0 for v in phases.values()), phases
    assert 0.95 * step_ms <= sum(phases.values()) <= 1.05 * step_ms, (
        phases, step_ms)
    # the overlap scatter-adds: fusions the TPU compiler left with no
    # op_name, placed in their phase through their operands
    prog = program_trace.read_file(PHASES)
    scatter = [o for o in prog.ops
               if not o.scope and "kind=kCustom" in o.name]
    assert scatter and {o.phase for o in scatter} == {"object_solve"}
