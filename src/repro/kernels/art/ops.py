"""The ART sweep over a block of slices (platform dispatch) and its row-norm
precompute."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.art import kernel, ref


def inverse_row_norms(A: jax.Array) -> jax.Array:
    """``1/‖A_j‖²`` of each row, 0 for an empty row (a no-op in the sweep)."""
    rip = jnp.sum(A * A, axis=1)
    return jnp.where(rip > 0, 1.0 / jnp.maximum(rip, 1e-12), 0.0)


def art_sweep_slices(A: jax.Array, B: jax.Array, inv_rip: jax.Array,
                     beta: float = 1.0, iters: int = 1,
                     use_pallas: bool | None = None) -> jax.Array:
    """Slices ``B`` (k, Nrow) from ``f0 = 0`` -> (k, Ncol), one system
    ``A`` (Nrow, Ncol) with its ``inv_rip`` shared by every slice. The
    kernel sweeps the slices together (``kernel.matrix_passes`` passes of
    ``A``); the reference sweeps each one alone."""
    use_pallas, interpret = dispatch.kernel_mode(use_pallas)
    if use_pallas:
        return kernel.art_sweep(A, B, inv_rip, beta=beta, iters=iters,
                                interpret=interpret)
    f0 = jnp.zeros((A.shape[1],), jnp.float32)
    return jax.vmap(lambda b: ref.art_sweep_ref(A, b, inv_rip, f0, beta=beta,
                                                iters=iters))(B)
