"""Jit'd wrapper for the RAAR combine (complex in/out, platform dispatch)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.raar import kernel, ref


def raar_combine(psi: jax.Array, p1: jax.Array, p21: jax.Array,
                 p2: jax.Array, beta: float = 0.75,
                 use_pallas: bool | None = None) -> jax.Array:
    use_pallas, interpret = dispatch.kernel_mode(use_pallas)
    if not use_pallas:
        return ref.raar_combine_complex(psi, p1, p21, p2, beta)
    planes = []
    for z in (psi, p1, p21, p2):
        planes += [jnp.real(z).astype(jnp.float32),
                   jnp.imag(z).astype(jnp.float32)]
    o_re, o_im = kernel.raar_combine(*planes, beta=beta,
                                     interpret=interpret)
    return jax.lax.complex(o_re, o_im)
