"""Device idle time inside the RDD scheduler's jobs per micro-batch, in ms:
each ``repro.rdd.job`` profiler span of the window (``TaskScheduler.run``:
the batch's collect, then the operator over the slice partitions) minus
chip 0's busy time in it, summed, over the window's batches."""
from chipbench import program_trace


def read(run):
    prog = program_trace.for_run(run)
    if prog is None or not run.facts.get("batches"):
        return None
    a, b = run.trace.window
    jobs = [s for s in prog.spans_named("repro.rdd.job")
            if a <= s.start_ns and s.end_ns <= b]
    if not jobs:
        return None
    idle = sum(s.seconds - run.trace.busy_between(s.start_ns, s.end_ns)
               for s in jobs)
    return 1e3 * idle / run.facts["batches"]
