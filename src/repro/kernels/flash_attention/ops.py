"""Jit'd wrapper: (B, S, H, hd) model layout -> kernel layout + dispatch.

Used by ``models/attention.py`` when ``attention_impl='pallas'``; pads S to
the block size, folds (B, H) into the kernel's batch axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.flash_attention import kernel, ref


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    qpos=None, kpos=None,
                    block_q: int = 256, block_kv: int = 512,
                    use_pallas: bool | None = None) -> jax.Array:
    """q, k, v: (B, S, H, hd) (KV already repeated to H). Causal."""
    use_pallas, interpret = dispatch.kernel_mode(use_pallas)
    B, S, H, hd = q.shape

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], hd)

    qb, kb, vb = map(to_bhsd, (q, k, v))
    bq = min(block_q, S)
    bkv = min(block_kv, S)
    pad = (-S) % bq if S % bq else 0
    pad = max(pad, (-S) % bkv if S % bkv else 0)
    if pad:
        qb = jnp.pad(qb, ((0, 0), (0, pad), (0, 0)))
        kb = jnp.pad(kb, ((0, 0), (0, pad), (0, 0)))
        vb = jnp.pad(vb, ((0, 0), (0, pad), (0, 0)))
    if use_pallas:
        out = kernel.flash_attention_bhsd(qb, kb, vb, block_q=bq,
                                          block_kv=bkv, causal=True,
                                          interpret=interpret)
    else:
        out = ref.attention_ref(qb, kb, vb, causal=True)
    out = out[:, :S]
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
