"""Jit'd wrapper for the ART sweep (platform dispatch + row-norm precompute)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.art import kernel, ref


def art_reconstruct_slice(A: jax.Array, b: jax.Array, f0: jax.Array,
                          beta: float = 1.0, iters: int = 1,
                          use_pallas: bool | None = None) -> jax.Array:
    """One tilt-series slice: A (Nrow, Ncol), b (Nrow,), f0 (Ncol,)."""
    use_pallas, interpret = dispatch.kernel_mode(use_pallas)
    rip = jnp.sum(A * A, axis=1)
    inv_rip = jnp.where(rip > 0, 1.0 / jnp.maximum(rip, 1e-12), 0.0)
    if use_pallas:
        return kernel.art_sweep(A, b, inv_rip, f0, beta=beta, iters=iters,
                                interpret=interpret)
    return ref.art_sweep_ref(A, b, inv_rip, f0, beta=beta, iters=iters)
