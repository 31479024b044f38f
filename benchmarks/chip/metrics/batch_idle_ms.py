"""Device idle time inside each of the window's micro-batches, in ms: the
pipeline's own ``repro.batch`` profiler span (from the batch's start to its
finished ``TraceLog`` span) minus chip 0's busy time in it, mean over the
batches whose span lies in the window."""
from chipbench import program_trace


def read(run):
    prog = program_trace.for_run(run)
    if prog is None:
        return None
    a, b = run.trace.window
    spans = [s for s in prog.spans_named("repro.batch")
             if a <= s.start_ns and s.end_ns <= b]
    if not spans:
        return None
    idle = [s.seconds - run.trace.busy_between(s.start_ns, s.end_ns)
            for s in spans]
    return 1e3 * sum(idle) / len(idle)
