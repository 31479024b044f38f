"""Mixture-of-Experts layer: top-k routing, capacity-based sort dispatch, EP.

TPU adaptation notes (DESIGN.md §2): there are no per-token atomics, so the
dispatch is restructured as dense, statically-shaped tensor ops —

  1. router: (T, D) @ (D, E) -> top-k gates/indices (fp32 softmax);
  2. position-in-expert via *sorted ranks* (argsort + searchsorted), which is
     O(T·k log) memory-lean versus the O(T·k·E) one-hot cumsum;
  3. scatter into an (E, C, D) capacity buffer (tokens over capacity drop —
     Switch-style; C = T·k/E · capacity_factor);
  4. batched expert matmuls einsum('ecd,edf->ecf') — MXU-shaped;
  5. gather-weighted combine back to (T, D).

Sharding: expert dim 'experts'->'model' (EP); capacity dim 'expert_cap'->
'data' keeps each data shard's tokens in its own capacity slice; for the 1T
config the expert weights additionally shard d_model over 'data'
('expert_in'->'data'), i.e. FSDP — XLA inserts the per-layer all-gather.
The router aux loss (load-balancing) follows Switch/GShard.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.layers import activation, normal_init, split_keys
from repro.parallel.sharding import logical_constraint


def padded_experts(config: ModelConfig) -> int:
    """The a2a path pads E to a multiple of the device count so each device
    owns whole experts (e.g. kimi: 384 -> 512 on 256 chips)."""
    pad_to = int(config.sharding_overrides.get("_moe_pad_experts", 0))
    if pad_to and config.sharding_overrides.get("_moe_impl") == "a2a":
        return -(-config.num_experts // pad_to) * pad_to
    return config.num_experts


def init_moe(key: jax.Array, config: ModelConfig, dtype: Any) -> tuple[dict, dict]:
    d, f = config.d_model, config.d_ff
    e = padded_experts(config)
    k1, k2, k3, k4 = split_keys(key, 4)
    std_in = 1.0 / np.sqrt(d)
    std_out = 1.0 / np.sqrt(f) / np.sqrt(2.0 * config.num_layers)
    params = {
        "router": normal_init(k1, (d, config.num_experts), std_in,
                              jnp.float32),
        "w_gate": normal_init(k2, (e, d, f), std_in, dtype),
        "w_up": normal_init(k3, (e, d, f), std_in, dtype),
        "w_down": normal_init(k4, (e, f, d), std_out, dtype),
    }
    ax = ("experts_a2a" if config.sharding_overrides.get("_moe_impl") ==
          "a2a" else "experts")
    in_ax = ("null" if ax == "experts_a2a" else "expert_in")
    specs = {
        "router": ("embed", "null"),
        "w_gate": (ax, in_ax, "ff"),
        "w_up": (ax, in_ax, "ff"),
        "w_down": (ax, "ff", in_ax),
    }
    return params, specs


def _positions_in_expert(expert_idx: jax.Array, num_experts: int) -> jax.Array:
    """Rank of each routed slot within its expert, via stable sort.

    expert_idx: (N,) int32 -> (N,) int32 position (0-based) among slots
    routed to the same expert, ordered by original index.
    """
    n = expert_idx.shape[0]
    order = jnp.argsort(expert_idx, stable=True)              # (N,)
    sorted_e = expert_idx[order]
    first = jnp.searchsorted(sorted_e, jnp.arange(num_experts),
                             side="left")                     # (E,)
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - first[sorted_e]
    pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)
    return pos


def moe_layer(x: jax.Array, params: dict, config: ModelConfig
              ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss)."""
    B, S, D = x.shape
    E, K = config.num_experts, config.experts_per_token
    T = B * S
    xt = x.reshape(T, D)

    # -- router (fp32) ----------------------------------------------------
    logits = xt.astype(jnp.float32) @ params["router"]          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, top_idx = jax.lax.top_k(probs, K)                    # (T, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux loss
    density = jnp.mean(jax.nn.one_hot(top_idx[:, 0], E, dtype=jnp.float32),
                       axis=0)
    router_mean = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * router_mean) * E * config.router_aux_loss

    # -- dispatch ----------------------------------------------------------
    capacity = int(max(1, np.ceil(T * K / E * config.capacity_factor)))
    slot_expert = top_idx.reshape(-1)                           # (T*K,)
    slot_token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)  # (T*K,)
    slot_gate = gates.reshape(-1)
    pos = _positions_in_expert(slot_expert, E)                  # (T*K,)
    keep = pos < capacity
    safe_pos = jnp.where(keep, pos, capacity - 1)

    buf = jnp.zeros((E, capacity, D), x.dtype)
    src = jnp.where(keep[:, None], xt[slot_token], 0).astype(x.dtype)
    buf = buf.at[slot_expert, safe_pos].add(src)                # (E, C, D)
    buf = logical_constraint(buf, "experts", "expert_cap", "embed")

    # -- expert compute (batched MXU matmuls) -----------------------------
    dtype = x.dtype
    up = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(dtype))
    gate = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(dtype))
    h = activation(gate, config.hidden_act) * up
    h = logical_constraint(h, "experts", "expert_cap", "ff")
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(dtype))

    # -- combine -------------------------------------------------------------
    slot_out = out_buf[slot_expert, safe_pos]                   # (T*K, D)
    slot_out = jnp.where(keep[:, None], slot_out, 0)
    combined = jax.ops.segment_sum(
        slot_out * slot_gate[:, None].astype(dtype), slot_token,
        num_segments=T)
    out = combined.reshape(B, S, D).astype(x.dtype)
    out = logical_constraint(out, "batch", "seq", "embed")
    return out, aux


# -- explicit all-to-all expert parallelism (§Perf, the Spark-MPI pattern) ----
def moe_layer_a2a(x: jax.Array, params: dict, config: ModelConfig
                  ) -> tuple[jax.Array, jax.Array]:
    """MoE with hand-placed all-to-all routing under shard_map.

    The GSPMD scatter-dispatch reshards the token stream against the
    expert-sharded capacity buffer with all-gathers (measured: the dominant
    ICI term of the 1T cell). This path does what an MPI program would do:
    each device owns E/n whole experts; tokens are routed with ONE
    all-to-all out and ONE back per layer — payload ≈ k·T_local·d_model,
    independent of E. Experts are padded to a device multiple
    (``_moe_pad_experts``).
    """
    from repro.parallel.sharding import current_mesh
    mesh = current_mesh()
    if mesh is None:
        return moe_layer(x, params, config)
    # expert ownership axis order must match the 'experts_a2a' rule
    # (('model','data')) or shard_map would reshard the weights
    axes = tuple(a for a in ("model", "data") if a in mesh.axis_names
                 and mesh.shape[a] > 1)
    if not axes:
        return moe_layer(x, params, config)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    E_pad = params["w_up"].shape[0]
    if E_pad % n_dev:
        return moe_layer(x, params, config)
    e_per = E_pad // n_dev
    E, K = config.num_experts, config.experts_per_token

    from jax.sharding import PartitionSpec as P

    def body(x, router, w_gate, w_up, w_down):
        B, S, D = x.shape                                  # local shapes
        T = B * S
        xt = x.reshape(T, D)
        logits = xt.astype(jnp.float32) @ router           # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, top_idx = jax.lax.top_k(probs, K)
        gates = (gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9))

        density = jnp.mean(jax.nn.one_hot(top_idx[:, 0], E,
                                          dtype=jnp.float32), axis=0)
        density = jax.lax.pmean(density, axes)
        router_mean = jax.lax.pmean(jnp.mean(probs, axis=0), axes)
        aux = jnp.sum(density * router_mean) * E * config.router_aux_loss

        # route slots to the owning device
        slot_expert = top_idx.reshape(-1)                  # (T*K,)
        slot_token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
        slot_gate = gates.reshape(-1).astype(jnp.float32)
        dest = slot_expert // e_per                        # (T*K,) device id
        cap = int(max(1, np.ceil(T * K / n_dev
                                 * config.capacity_factor)))
        pos = _positions_in_expert(dest, n_dev)
        keep = pos < cap
        safe_pos = jnp.where(keep, pos, cap - 1)
        send_x = jnp.zeros((n_dev, cap, D), x.dtype).at[dest, safe_pos].add(
            jnp.where(keep[:, None], xt[slot_token], 0).astype(x.dtype))
        send_e = jnp.full((n_dev, cap), -1, jnp.int32).at[
            dest, safe_pos].max(jnp.where(keep, slot_expert, -1))
        send_g = jnp.zeros((n_dev, cap), jnp.float32).at[
            dest, safe_pos].add(jnp.where(keep, slot_gate, 0.0))

        recv_x = jax.lax.all_to_all(send_x, axes, 0, 0, tiled=True)
        recv_e = jax.lax.all_to_all(send_e, axes, 0, 0, tiled=True)
        recv_g = jax.lax.all_to_all(send_g, axes, 0, 0, tiled=True)
        R = n_dev * cap
        rx = recv_x.reshape(R, D)
        my_lo = jax.lax.axis_index(axes) * e_per
        le = recv_e.reshape(R) - my_lo                     # local expert id
        valid = (le >= 0) & (le < e_per)

        # local re-dispatch into (e_per, cap_loc, D)
        le_safe = jnp.where(valid, le, e_per - 1)
        lpos = _positions_in_expert(le_safe, e_per)
        cap_loc = R                                        # no second drop
        buf = jnp.zeros((e_per, cap_loc, D), x.dtype).at[
            le_safe, lpos].add(jnp.where(valid[:, None], rx, 0)
                               .astype(x.dtype))
        dtype = x.dtype
        up = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(dtype))
        gate = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(dtype))
        h = activation(gate, config.hidden_act) * up
        out_buf = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dtype))
        ry = jnp.where(valid[:, None], out_buf[le_safe, lpos], 0)
        ry = ry * recv_g.reshape(R, 1).astype(dtype)
        back = jax.lax.all_to_all(ry.reshape(n_dev, cap, D), axes, 0, 0,
                                  tiled=True)
        slot_out = jnp.where(keep[:, None], back[dest, safe_pos], 0)
        combined = jax.ops.segment_sum(slot_out.astype(jnp.float32),
                                       slot_token, num_segments=T)
        return combined.reshape(B, S, D).astype(x.dtype), aux

    # x arrives (batch@[pod,]data, act_seq@model); weights are per-device
    # expert blocks (pod-replicated: pod stays pure DP)
    bspec = (("pod", "data") if "pod" in mesh.axis_names else "data")
    in_specs = (P(bspec, "model", None), P(None, None),
                P(axes, None, None), P(axes, None, None),
                P(axes, None, None))
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(bspec, "model", None), P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    return out, aux
