"""Passes of the ART system matrix per slice reconstructed: the ``passes``
over the ``slices`` of the program's ``repro.tomo.sweep`` profiler spans
that start in the window (one span per partition's call of the operator).
A program without the span reads nothing."""
from chipbench import program_trace


def read(run):
    prog = program_trace.for_run(run)
    if prog is None:
        return None
    a, b = run.trace.window
    sweeps = [s for s in prog.spans_named("repro.tomo.sweep")
              if a <= s.start_ns < b]
    slices = sum(float(s.args.get("slices", 0)) for s in sweeps)
    if not slices:
        return None
    return sum(float(s.args.get("passes", 0)) for s in sweeps) / slices
