"""Attention: GQA/MQA/MHA, RoPE, causal + sliding-window + cross, three impls.

* ``naive``   — full score matrix; oracle for tests and small shapes.
* ``blocked`` — double-scan online-softmax (flash-style dataflow in pure JAX):
                O(block_q × block_kv) live scores, exact same math. This is
                the default for long sequences on any backend.
* ``pallas``  — the TPU flash kernel in ``kernels/flash_attention`` (same
                blocking, VMEM-resident); validated against ``naive`` in
                interpret mode, selected via ``attention_impl='pallas'``.

GQA: K/V are repeated to the full H query heads *after* projection (and
after RoPE), keeping every attention tensor 4-D (B, S, H, hd) — the only
layout where TP-by-heads shards cleanly under GSPMD (a grouped 5-D
(B, S, KH, G, hd) layout splits the 'model' axis across two dims, which
GSPMD cannot express; it then invents cross-shard contractions — observed
as per-tile all-reduces in the lowered HLO). The
repeat is free on the wire (slices locally) and the Pallas kernel avoids
the HBM copy on real hardware.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, normal_init, split_keys
from repro.parallel.sharding import logical_constraint

NEG_INF = -1e30


# -- params --------------------------------------------------------------------
def init_attention(key: jax.Array, config: ModelConfig, dtype: Any,
                   cross: bool = False, num_heads: int | None = None,
                   num_kv_heads: int | None = None) -> tuple[dict, dict]:
    d = config.d_model
    h = num_heads or config.num_heads
    kh = num_kv_heads or config.num_kv_heads
    hd = config.resolved_head_dim
    k1, k2, k3, k4 = split_keys(key, 4)
    std = 1.0 / np.sqrt(d)
    std_o = 1.0 / np.sqrt(h * hd) / np.sqrt(2.0 * config.num_layers)
    params = {
        "wq": normal_init(k1, (d, h * hd), std, dtype),
        "wk": normal_init(k2, (d, kh * hd), std, dtype),
        "wv": normal_init(k3, (d, kh * hd), std, dtype),
        "wo": normal_init(k4, (h * hd, d), std_o, dtype),
    }
    specs = {"wq": ("embed_fsdp", "heads"), "wk": ("embed_fsdp", "kv_heads"),
             "wv": ("embed_fsdp", "kv_heads"), "wo": ("heads", "embed_fsdp")}
    return params, specs


# -- masking ---------------------------------------------------------------
def _pair_mask(qpos: jax.Array, kpos: jax.Array, causal: bool,
               window: int) -> jax.Array:
    """(B, Sq, Skv) boolean mask. kpos < 0 marks padding/invalid slots."""
    valid = kpos[:, None, :] >= 0
    if causal:
        valid &= kpos[:, None, :] <= qpos[:, :, None]
    if window > 0:
        valid &= qpos[:, :, None] - kpos[:, None, :] < window
    return valid


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    return x.reshape(x.shape[:-1] + (n, hd))


# -- naive (oracle) -----------------------------------------------------------
def naive_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    qpos: jax.Array, kpos: jax.Array,
                    causal: bool = True, window: int = 0) -> jax.Array:
    """q, k, v: (B, S, H, hd) (KV already repeated) -> (B, Sq, H, hd)."""
    hd = q.shape[-1]
    scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = _pair_mask(qpos, kpos, causal, window)            # (B,Sq,Skv)
    s = jnp.where(mask[:, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# -- blocked (flash dataflow, pure JAX) --------------------------------------
def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      qpos: jax.Array, kpos: jax.Array,
                      causal: bool = True, window: int = 0,
                      block_q: int = 512, block_kv: int = 1024,
                      skip_blocks: bool = False) -> jax.Array:
    """Online-softmax over (q-block × kv-block) tiles via nested lax.scan.

    ``skip_blocks=True`` enables the triangular schedule: kv blocks entirely
    above the causal diagonal (or outside the sliding window) contribute a
    zero-FLOP branch via lax.cond — the §Perf causal-skipping optimization.
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    scale = 1.0 / np.sqrt(hd)
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    nq, nk = -(-Sq // bq), -(-Skv // bkv)
    pq, pk = nq * bq - Sq, nk * bkv - Skv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq)) + ((0, 0),) * 2)
        qpos = jnp.pad(qpos, ((0, 0), (0, pq)), constant_values=-1)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, ((0, 0), (0, pk)), constant_values=-1)

    # (n, B, blk, ...) layouts for scan
    qb = q.reshape(B, nq, bq, H, hd).transpose(1, 0, 2, 3, 4)
    qpb = qpos.reshape(B, nq, bq).transpose(1, 0, 2)
    kb = k.reshape(B, nk, bkv, H, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, bkv, H, hd).transpose(1, 0, 2, 3, 4)
    kpb = kpos.reshape(B, nk, bkv).transpose(1, 0, 2)

    def q_step(_, qc):
        q_i, qp_i, qi = qc
        m0 = jnp.full((B, H, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, bq), jnp.float32)
        a0 = jnp.zeros((B, bq, H, hd), jnp.float32)

        def tile(q_i, qp_i, k_j, v_j, kp_j, m, l, acc):
            s = jnp.einsum("bqhd,bshd->bhqs", q_i.astype(jnp.float32),
                           k_j.astype(jnp.float32)) * scale
            mask = _pair_mask(qp_i, kp_j, causal, window)
            s = jnp.where(mask[:, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqs,bshd->bqhd", p, v_j.astype(jnp.float32))
            acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv
            return m_new, l_new, acc_new

        def kv_step(carry, kc):
            m, l, acc = carry
            k_j, v_j, kp_j, kj = kc
            if skip_blocks:
                # Block-level reachability from static block layout:
                # any (q,k) pair in-tile can be unmasked?
                q_lo = qi * bq
                k_lo, k_hi = kj * bkv, kj * bkv + bkv - 1
                reachable = jnp.asarray(True)
                if causal:  # kv block entirely in the future -> skip
                    q_hi = qi * bq + bq - 1
                    reachable = k_lo <= q_hi
                if window > 0:  # kv block entirely before the window -> skip
                    reachable = jnp.logical_and(reachable,
                                                q_lo - k_hi < window)
                m, l, acc = jax.lax.cond(
                    reachable,
                    lambda m, l, acc: tile(q_i, qp_i, k_j, v_j, kp_j, m, l, acc),
                    lambda m, l, acc: (m, l, acc),
                    m, l, acc)
            else:
                m, l, acc = tile(q_i, qp_i, k_j, v_j, kp_j, m, l, acc)
            return (m, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kb, vb, kpb, jnp.arange(nk)))
        lt = l.transpose(0, 2, 1)[..., None]
        out_i = acc / jnp.maximum(lt, 1e-30)
        return None, out_i.astype(q_i.dtype)

    _, out = jax.lax.scan(q_step, None, (qb, qpb, jnp.arange(nq)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, nq * bq, H, hd)
    return out[:, :Sq]


# -- triangular schedule (flattened causal block sweep) -----------------------
def triangular_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         qpos: jax.Array, kpos: jax.Array,
                         causal: bool = True, window: int = 0,
                         block: int = 512) -> jax.Array:
    """Causal blocked attention that only *issues* reachable tiles.

    The rectangular double-scan masks unreachable (q, kv) tiles but still
    executes their FLOPs; this schedule flattens the valid tile list —
    n(n+1)/2 instead of n² for causal, fewer still with a window — into ONE
    scan, so the savings are structural (visible to the HLO walker / real on
    hardware). §Perf optimization for compute-bound prefill/train cells.
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    assert Sq == Skv, "triangular schedule is for self-attention"
    b = min(block, Sq)
    n = -(-Sq // b)
    pad = n * b - Sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad)), constant_values=-1)
        kpos = jnp.pad(kpos, ((0, 0), (0, pad)), constant_values=-1)
    scale = 1.0 / np.sqrt(hd)
    qb = q.reshape(B, n, b, H, hd).transpose(1, 0, 2, 3, 4)
    kb = k.reshape(B, n, b, H, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n, b, H, hd).transpose(1, 0, 2, 3, 4)
    qpb = qpos.reshape(B, n, b).transpose(1, 0, 2)
    kpb = kpos.reshape(B, n, b).transpose(1, 0, 2)

    pairs = [(qi, ki) for qi in range(n) for ki in range(qi + 1)
             if window <= 0 or qi * b - (ki * b + b - 1) < window]
    pair_q = jnp.asarray([p[0] for p in pairs], jnp.int32)
    pair_k = jnp.asarray([p[1] for p in pairs], jnp.int32)

    m0 = jnp.full((n, B, H, b), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n, B, H, b), jnp.float32)
    a0 = jnp.zeros((n, B, b, H, hd), jnp.float32)

    def step(carry, pair):
        m, l, acc = carry
        qi, ki = pair
        q_i = jax.lax.dynamic_index_in_dim(qb, qi, 0, keepdims=False)
        qp_i = jax.lax.dynamic_index_in_dim(qpb, qi, 0, keepdims=False)
        k_j = jax.lax.dynamic_index_in_dim(kb, ki, 0, keepdims=False)
        v_j = jax.lax.dynamic_index_in_dim(vb, ki, 0, keepdims=False)
        kp_j = jax.lax.dynamic_index_in_dim(kpb, ki, 0, keepdims=False)
        s = jnp.einsum("bqhd,bshd->bhqs", q_i.astype(jnp.float32),
                       k_j.astype(jnp.float32)) * scale
        mask = _pair_mask(qp_i, kp_j, causal, window)
        s = jnp.where(mask[:, None, :, :], s, NEG_INF)
        m_i = jax.lax.dynamic_index_in_dim(m, qi, 0, keepdims=False)
        l_i = jax.lax.dynamic_index_in_dim(l, qi, 0, keepdims=False)
        a_i = jax.lax.dynamic_index_in_dim(acc, qi, 0, keepdims=False)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_i - m_new)
        l_new = l_i * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqs,bshd->bqhd", p, v_j.astype(jnp.float32))
        a_new = a_i * alpha.transpose(0, 2, 1)[..., None] + pv
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, qi, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, qi, 0)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, qi, 0)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (pair_q, pair_k))
    lt = l.transpose(0, 1, 3, 2)[..., None]                 # (n,B,b,H,1)
    out = (acc / jnp.maximum(lt, 1e-30)).astype(q.dtype)
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, n * b, H, hd)
    return out[:, :Sq]


# -- dispatch -------------------------------------------------------------------
def attention_core(q: jax.Array, k: jax.Array, v: jax.Array,
                   qpos: jax.Array, kpos: jax.Array, config: ModelConfig,
                   causal: bool = True, window: int = 0) -> jax.Array:
    impl = config.attention_impl
    Sq = q.shape[1]
    if impl == "pallas" and causal and window == 0 and Sq > 1:
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k, v, qpos, kpos)
    if impl == "naive" or Sq == 1 or q.shape[1] <= config.attention_block_q:
        return naive_attention(q, k, v, qpos, kpos, causal, window)
    if impl == "triangular" and causal and Sq == k.shape[1]:
        return triangular_attention(q, k, v, qpos, kpos, causal, window,
                                    block=config.attention_block_q)
    return blocked_attention(
        q, k, v, qpos, kpos, causal, window,
        block_q=config.attention_block_q, block_kv=config.attention_block_kv,
        skip_blocks=config.sharding_overrides.get("_skip_blocks", False))


def attention_layer(x: jax.Array, params: dict, config: ModelConfig,
                    positions: jax.Array,
                    cache: dict | None = None,
                    kv_source: jax.Array | None = None,
                    precomputed_kv: tuple[jax.Array, jax.Array] | None = None,
                    causal: bool = True, window: int = 0,
                    num_heads: int | None = None,
                    num_kv_heads: int | None = None
                    ) -> tuple[jax.Array, dict | None]:
    """Full attention layer: qkv proj, rope, core, out proj.

    ``cache`` (decode/prefill): dict with 'k','v' (B, Smax, KH, hd) rolling
    buffers and scalar 'pos' (tokens already cached). ``kv_source`` switches
    to cross-attention (keys/values projected from encoder output);
    ``precomputed_kv`` reuses cached cross K/V at decode time.
    """
    B, S, _ = x.shape
    h = num_heads or config.num_heads
    kh = num_kv_heads or config.num_kv_heads
    hd = config.resolved_head_dim
    g = h // kh
    dtype = x.dtype

    q = _split_heads(x @ params["wq"].astype(dtype), h, hd)
    if precomputed_kv is not None:
        k, v = precomputed_kv
    else:
        src = x if kv_source is None else kv_source
        k = _split_heads(src @ params["wk"].astype(dtype), kh, hd)
        v = _split_heads(src @ params["wv"].astype(dtype), kh, hd)

    cross = kv_source is not None or precomputed_kv is not None
    if config.pos_embedding == "rope" and not cross:
        q = apply_rope(q, positions, config.rope_theta)
        k = apply_rope(k, positions, config.rope_theta)

    q = logical_constraint(q, "batch", "seq", "heads", "head_dim")
    k = logical_constraint(k, "batch", "seq", "kv_heads", "head_dim")
    v = logical_constraint(v, "batch", "seq", "kv_heads", "head_dim")

    # Head padding: when the TP degree does not divide H (llava 56/16,
    # starcoder2 24/16, rgemma 10/16), unsharded heads would REPLICATE the
    # whole attention computation on every model shard. Padding H to the
    # next multiple trades (H'/H - 1) extra FLOPs for a 1/m shard — e.g.
    # llava: 64/56 = 1.14x work instead of 16x. §Perf optimization.
    from repro.parallel.sharding import current_mesh
    mesh = current_mesh()
    m = (mesh.shape.get("model", 1) if mesh is not None else 1)
    pad_h = (-h) % m if (config.pad_attention_heads and m > 1) else 0
    if pad_h:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_h), (0, 0)))
        q = logical_constraint(q, "batch", "seq", "heads", "head_dim")

    def rep(t):
        # repeat KV to full H heads (4-D TP-by-heads layout; see module doc)
        t = jnp.repeat(t, g, axis=2) if g > 1 else t
        if pad_h:
            t = jnp.pad(t, ((0, 0), (0, 0), (0, pad_h), (0, 0)))
        return logical_constraint(t, "batch", "seq", "heads", "head_dim")

    new_cache = None
    if cross:
        # cross attention: all encoder positions visible
        kpos = jnp.broadcast_to(jnp.arange(k.shape[1]), (B, k.shape[1]))
        out = attention_core(q, rep(k), rep(v), positions, kpos, config,
                             causal=False, window=0)
        new_cache = {"k": k, "v": v}
    elif cache is None:
        out = attention_core(q, rep(k), rep(v), positions, positions, config,
                             causal=causal, window=window)
    elif S > 1:
        # prefill: attend over the fresh sequence, then fill the cache
        out = attention_core(q, rep(k), rep(v), positions, positions, config,
                             causal=causal, window=window)
        ck, cv, pos = cache["k"], cache["v"], cache["pos"]
        Smax = ck.shape[1]
        if window > 0 and S >= Smax:
            # keep the last window, rotated so slot(p) == p % Smax
            shift = (S - Smax) % Smax
            ck = jnp.roll(k[:, S - Smax:].astype(ck.dtype), shift, axis=1)
            cv = jnp.roll(v[:, S - Smax:].astype(cv.dtype), shift, axis=1)
        else:
            ck = jax.lax.dynamic_update_slice(
                ck, k[:, :Smax].astype(ck.dtype), (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cv, v[:, :Smax].astype(cv.dtype), (0, pos, 0, 0))
        new_cache = {"k": ck, "v": cv, "pos": pos + S}
    else:
        # decode with rolling-buffer cache (window archs wrap in-place)
        ck, cv, pos = cache["k"], cache["v"], cache["pos"]
        Smax = ck.shape[1]
        slot = (pos % Smax) if window > 0 else jnp.minimum(pos, Smax - 1)
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, slot, 0, 0))
        # absolute positions of cache slots; -1 marks not-yet-filled
        idx = jnp.arange(Smax)
        if window > 0:
            abs_pos = idx + ((pos - idx) // Smax) * Smax
            kpos_row = jnp.where((abs_pos >= 0) & (abs_pos <= pos),
                                 abs_pos, -1)
        else:
            kpos_row = jnp.where(idx <= pos, idx, -1)
        kpos = jnp.broadcast_to(kpos_row, (B, Smax))
        out = attention_core(q, rep(ck), rep(cv), positions, kpos, config,
                             causal=True, window=window)
        new_cache = {"k": ck, "v": cv, "pos": pos + 1}

    if pad_h:
        out = out[:, :, :h]
    out = out.reshape(B, S, h * hd)
    out = out @ params["wo"].astype(dtype)
    return out, new_cache


def init_cache(config: ModelConfig, batch: int, max_len: int,
               window: int = 0, dtype: Any = None,
               num_kv_heads: int | None = None) -> dict:
    kh = num_kv_heads or config.num_kv_heads
    hd = config.resolved_head_dim
    size = min(window, max_len) if window > 0 else max_len
    dtype = dtype or config.activation_dtype
    return {
        "k": jnp.zeros((batch, size, kh, hd), dtype),
        "v": jnp.zeros((batch, size, kh, hd), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


CACHE_SPECS = {"k": ("batch", "null", "kv_heads", "head_dim"),
               "v": ("batch", "null", "kv_heads", "head_dim"),
               "pos": ()}
