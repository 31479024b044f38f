"""Jit'd wrappers for the overlap kernels (complex in/out, dispatch)."""
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
    n_re, n_im, den = kernel.overlap_products(
        jnp.real(a).astype(jnp.float32), jnp.imag(a).astype(jnp.float32),
        jnp.real(b).astype(jnp.float32), jnp.imag(b).astype(jnp.float32),
        interpret=interpret)
    return jax.lax.complex(n_re, n_im), den


def overlap_scatter(psi: jax.Array, probe: jax.Array, positions: jax.Array,
                    obj_shape: tuple[int, int],
                    use_pallas: bool | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """ψ complex (F, n, n), probe complex (n, n), positions (F, 2) ->
    (Σ_j place_j(ψ_j·conj(P)) complex, Σ_j place_j(|P|²) fp32), each of
    ``obj_shape``. Positions must lie in [0, H-n] × [0, W-n]. The kernel
    raises ``ValueError`` for a canvas and frames past a v5e's VMEM
    (``kernel.overlap_scatter``); ``use_pallas=False`` has no such bound."""
    use_pallas, interpret = dispatch.kernel_mode(use_pallas)
    if not use_pallas:
        return ref.overlap_scatter_complex(psi, probe, positions, obj_shape)
    n_re, n_im, den = kernel.overlap_scatter(
        jnp.real(psi).astype(jnp.float32), jnp.imag(psi).astype(jnp.float32),
        jnp.real(probe).astype(jnp.float32),
        jnp.imag(probe).astype(jnp.float32), positions,
        obj_shape=tuple(int(v) for v in obj_shape), interpret=interpret)
    return jax.lax.complex(n_re, n_im), den
