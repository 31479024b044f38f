"""Device busy time of one RAAR iteration over a whole scan's frames: the
busy time inside the refinement spans, over the refinement iterations, in
ms."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans_named("bench.ptycho.refine")
    if not spans:
        return None
    busy = sum(run.trace.busy_between(s.start_ns, s.end_ns) for s in spans)
    iters = run.facts["refine_iterations"] * len(spans)
    return 1e3 * busy / iters if busy > 0 else None
