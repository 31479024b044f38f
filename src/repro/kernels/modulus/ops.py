"""Jit'd wrapper: platform dispatch for the modulus projection.

On a TPU the Pallas kernel runs compiled; elsewhere the pure-jnp reference
runs unless a test forces the kernel, which then runs in interpret mode
(same kernel body, Python-interpreted) — see ``kernels/dispatch.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.modulus import kernel, ref


def modulus_project(psi_f: jax.Array, mag: jax.Array,
                    use_pallas: bool | None = None) -> jax.Array:
    """psi_f: complex64 (F, H, W); mag: fp32 (F, H, W) -> complex64."""
    use_pallas, interpret = dispatch.kernel_mode(use_pallas)
    re = jnp.real(psi_f).astype(jnp.float32)
    im = jnp.imag(psi_f).astype(jnp.float32)
    if use_pallas:
        ore, oim = kernel.modulus_project(re, im, mag, interpret=interpret)
    else:
        ore, oim = ref.modulus_project_ref(re, im, mag)
    return jax.lax.complex(ore, oim)
