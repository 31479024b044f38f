"""Pure-jnp oracles for the overlap kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def overlap_products_ref(a_re, a_im, b_re, b_im):
    n_re = a_re * b_re + a_im * b_im
    n_im = a_im * b_re - a_re * b_im
    den = b_re * b_re + b_im * b_im
    return n_re, n_im, den


def overlap_products_complex(a: jax.Array, b: jax.Array
                             ) -> tuple[jax.Array, jax.Array]:
    """(a · conj(b), |b|²)."""
    return a * jnp.conj(b), jnp.square(jnp.abs(b))


def overlap_scatter_complex(psi: jax.Array, probe: jax.Array,
                            positions: jax.Array, obj_shape: tuple[int, int]
                            ) -> tuple[jax.Array, jax.Array]:
    """(Σ_j place_j(ψ_j · conj(P)), Σ_j place_j(|P|²)) as XLA's per-pixel
    scatter-add."""
    rows = jnp.arange(psi.shape[-1])
    iy = positions[:, 0, None, None] + rows[None, :, None]
    ix = positions[:, 1, None, None] + rows[None, None, :]
    num_o, den_o = overlap_products_complex(
        psi, jnp.broadcast_to(probe[None], psi.shape))
    num = jnp.zeros(obj_shape, psi.dtype).at[iy, ix].add(num_o)
    den = jnp.zeros(obj_shape, jnp.float32).at[iy, ix].add(den_o)
    return num, den
