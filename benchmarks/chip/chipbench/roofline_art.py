"""Operations and bytes of the ART sweep's own work, from its shapes.

They count what the algorithm needs, not what an implementation happens to
do (``chipbench.roofline``'s rule): the dense system matrix read once per
sweep for the whole micro-batch, however many slices share the pass, so a
kernel that streams the matrix once per slice, as a ``vmap`` over slices
does, reads far above this and shows it as a low share. The count holds for
a dense ``A``; a matrix-free projector, which computes each row's weights
on the fly instead of reading them, would need a new count.
"""
from __future__ import annotations

F32 = 4


def art_batch(slices: int, nrow: int, ncol: int, sweeps: int
              ) -> tuple[float, float]:
    """One micro-batch of ``slices`` slices, ``sweeps`` sweeps over an
    (nrow, ncol) float32 system: (FLOPs, bytes).

    FLOPs: per slice, sweep and row, the dot ⟨A_j, f⟩ (2 per column) and
    the update f += c·A_j (2 per column): 4·nrow·ncol.
    Bytes: ``A`` read once per sweep, the slices' sinogram rows read once,
    their images written once.
    """
    flops = 4.0 * nrow * ncol * slices * sweeps
    nbytes = (sweeps * nrow * ncol + slices * nrow + slices * ncol) * F32
    return float(flops), float(nbytes)
