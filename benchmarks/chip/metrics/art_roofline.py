"""Share of the roofline of the ART sweep over one micro-batch: the least
time its algorithmic work needs on this chip
(``chipbench.roofline_art.art_batch``: the dense matrix read once per sweep,
memory-bound) over the measured sweep time per batch (chip 0's
``art/sweep`` ops in the window over its batches), in %."""
from chipbench import art_trace, roofline


def read(run):
    seconds = art_trace.seconds(run)
    if not seconds:
        return None
    least, _ = roofline.least_time(*run.facts["art_work"], run.peaks)
    return 100.0 * least * run.facts["batches"] / seconds
