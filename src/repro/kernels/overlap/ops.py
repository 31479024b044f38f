"""Jit'd wrapper for overlap products (complex in/out, platform dispatch)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.overlap import kernel, ref


def overlap_products(a: jax.Array, b: jax.Array,
                     use_pallas: bool | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """a, b complex (F, H, W) -> (a·conj(b) complex, |b|² fp32)."""
    use_pallas, interpret = dispatch.kernel_mode(use_pallas)
    if not use_pallas:
        return ref.overlap_products_complex(a, b)
    b = jnp.broadcast_to(b, a.shape)
    n_re, n_im, den = kernel.overlap_products(
        jnp.real(a).astype(jnp.float32), jnp.imag(a).astype(jnp.float32),
        jnp.real(b).astype(jnp.float32), jnp.imag(b).astype(jnp.float32),
        interpret=interpret)
    return jax.lax.complex(n_re, n_im), den
