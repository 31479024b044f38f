"""Speculative task copies the RDD scheduler launched per micro-batch: the
``repro.rdd.task`` profiler spans that start in the window with
``speculative`` set, over the window's batches. On one chip a copy is the
same device work again."""
from chipbench import program_trace


def read(run):
    prog = program_trace.for_run(run)
    if prog is None or not run.facts.get("batches"):
        return None
    a, b = run.trace.window
    tasks = [s for s in prog.spans_named("repro.rdd.task")
             if a <= s.start_ns < b]
    if not tasks:
        return None
    copies = sum(bool(s.args.get("speculative")) for s in tasks)
    return copies / run.facts["batches"]
