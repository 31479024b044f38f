"""Device time of the ART sweep per slice reconstructed, in ms: chip 0's ops
in the window whose scope path holds ``art/sweep`` (the operator's
``jax.named_scope``), or that are the ``art_sweep`` kernel itself, over the
slices the window's micro-batches reconstructed."""
from chipbench import art_trace


def read(run):
    seconds = art_trace.seconds(run)
    if not seconds:
        return None
    return 1e3 * seconds / run.facts["slices"]
