"""Share of the roofline of one RAAR iteration over a whole scan's frames:
the least time its algorithmic work needs on this chip
(``chipbench.roofline.raar_iteration``; memory bounds it at Table II size)
over the measured ``raar_iter_ms``, in %."""
from chipbench import roofline


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans_named("bench.ptycho.refine")
    busy = sum(run.trace.busy_between(s.start_ns, s.end_ns) for s in spans)
    if busy <= 0:
        return None
    per_iter = busy / (run.facts["refine_iterations"] * len(spans))
    least, _ = roofline.least_time(*run.facts["raar_work"], run.peaks)
    return 100.0 * least / per_iter
