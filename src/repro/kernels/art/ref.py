"""Pure-jnp oracle for the ART sweep (paper Fig. 12 inner loop)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def art_sweep_ref(A: jax.Array, b: jax.Array, inv_rip: jax.Array,
                  f0: jax.Array, beta: float = 1.0,
                  iters: int = 1) -> jax.Array:
    def row_step(f, xs):
        row, bj, irip = xs
        # full f32 dot: on a TPU the default matmul precision is bf16
        resid = (bj - jnp.dot(row, f, precision="highest")) * irip
        return f + beta * resid * row, None

    def sweep(f, _):
        f, _ = jax.lax.scan(row_step, f, (A, b, inv_rip))
        return f, None

    f, _ = jax.lax.scan(sweep, f0, None, length=iters)
    return f
