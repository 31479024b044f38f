"""Explicit-collective data-parallel training (the Spark-MPI path).

GSPMD emits whatever collectives it likes; this module instead writes the
distributed optimizer the way the paper writes MPI programs — as an explicit
rank-parallel ``shard_map`` with hand-placed collectives:

    grads  --reduce-scatter-->  1/W flat shard        (psum_scatter)
    AdamW on the shard          (ZeRO: m/v/master live sharded, flat)
    params <--all-gather--      updated flat shards   (all_gather)

plus the paper's "future upgrade": int8-compressed gradient reduction with
a pmax-shared scale (optim/compression.py) — wire bytes ÷2 vs bf16, ÷4 vs
fp32, exact int32 summation.

This is the right layout when the model is small relative to the mesh
(§Perf: a 1.8B model on 256 chips is collective-bound under TP-16; pure DP
with ZeRO + compression moves the bottleneck back to compute). Numerics are
tested against the fused-GSPMD trainer in tests/test_dp.py.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, OptimizerConfig
from repro.models.registry import get_model
from repro.optim.adamw import lr_schedule
from repro.parallel.sharding import use_mesh
from repro.utils import get_logger

log = get_logger(__name__)


def _world(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


def flatten_params(params: Any, world: int) -> tuple[jax.Array, Any]:
    """Concatenate every leaf into one fp32 vector padded to world."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    flat = jnp.concatenate([l.astype(jnp.float32).reshape(-1)
                            for l in leaves])
    pad = (-flat.shape[0]) % world
    if pad:
        flat = jnp.pad(flat, (0, pad))
    meta = (treedef, [(l.shape, l.dtype) for l in leaves], pad)
    return flat, meta


def unflatten_params(flat: jax.Array, meta: Any) -> Any:
    treedef, shapes, pad = meta
    if pad:
        flat = flat[:-pad] if pad else flat
    out = []
    off = 0
    for shape, dtype in shapes:
        n = int(np.prod(shape))
        out.append(flat[off:off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def init_dp_opt_state(params: Any, mesh: Mesh,
                      opt: OptimizerConfig) -> dict:
    """Flat ZeRO shards, materialized with the correct sharding."""
    world = _world(mesh)
    flat, meta = flatten_params(params, world)
    chunk = flat.shape[0] // world
    axes = tuple(mesh.axis_names)
    shard = NamedSharding(mesh, P(axes))
    zeros = jnp.zeros((world * chunk,), jnp.dtype(opt.state_dtype))
    state = {
        "m": jax.device_put(zeros, shard),
        "v": jax.device_put(zeros, shard),
        "master": jax.device_put(flat, shard),
        "step": jnp.zeros((), jnp.int32),
    }
    return state


def build_dp_train_step(config: ModelConfig, opt: OptimizerConfig,
                        mesh: Mesh, compression: str | None = None):
    """Returns (jitted_step, state_shardings). state = {params, opt}."""
    model = get_model(config)
    world = _world(mesh)
    axes = tuple(mesh.axis_names)

    def step(state: dict, batch: dict) -> tuple[dict, dict]:
        with use_mesh(None):                       # manual collectives only
            params = state["params"]

            def loss_fn(p):
                return model.loss_and_metrics(p, batch, config)

            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            gflat, meta = flatten_params(grads, world)
            chunk = gflat.shape[0] // world
            g2d = gflat.reshape(world, chunk)
            if compression == "int8":
                # shared scale -> int8 ON THE WIRE (all-to-all) -> exact
                # int32 summation locally. (A psum_scatter of int32 would
                # be numerically identical but moves 4-byte words — the
                # first int8 attempt measured ZERO wire savings; see
                # EXPERIMENTS.md §Perf C2.)
                amax = jax.lax.pmax(jnp.max(jnp.abs(g2d)), axes)
                scale = jnp.maximum(amax / 127.0, 1e-12)
                q = jnp.clip(jnp.round(g2d / scale), -127, 127
                             ).astype(jnp.int8)
                qt = jax.lax.all_to_all(q, axes, 0, 0, tiled=False)
                qs = jnp.sum(qt.astype(jnp.int32), axis=0)
                g_shard = qs.astype(jnp.float32) * scale / world
            else:
                g_shard = jax.lax.psum_scatter(
                    g2d, axes, scatter_dimension=0, tiled=False) / world

            # global grad-norm clip on shards
            o = state["opt"]
            step_no = o["step"] + 1
            gn2 = jax.lax.psum(jnp.sum(jnp.square(g_shard)), axes)
            gnorm = jnp.sqrt(gn2)
            if opt.grad_clip > 0:
                g_shard = g_shard * jnp.minimum(
                    1.0, opt.grad_clip / jnp.maximum(gnorm, 1e-9))

            # AdamW on the flat shard (ZeRO-sharded m/v/master)
            lr = lr_schedule(step_no, opt)
            b1, b2 = opt.b1, opt.b2
            c1 = 1.0 - b1 ** step_no.astype(jnp.float32)
            c2 = 1.0 - b2 ** step_no.astype(jnp.float32)
            m = b1 * o["m"].astype(jnp.float32) + (1 - b1) * g_shard
            v = b2 * o["v"].astype(jnp.float32) + (1 - b2) * g_shard ** 2
            delta = (m / c1) / (jnp.sqrt(v / c2) + opt.eps)
            master = o["master"] - lr * (delta + opt.weight_decay
                                         * o["master"])
            # gather the update in bf16: params are bf16, so gathering the
            # fp32 master doubles the wire for nothing (§Perf C3)
            new_flat = jax.lax.all_gather(master.astype(jnp.bfloat16),
                                          axes, axis=0, tiled=True)
            new_params = jax.tree_util.tree_map(
                lambda a, b: a.astype(b.dtype),
                unflatten_params(new_flat, meta), params)
            sd = jnp.dtype(opt.state_dtype)
            new_state = {"params": new_params,
                         "opt": {"m": m.astype(sd), "v": v.astype(sd),
                                 "master": master, "step": step_no}}
            metrics = {**metrics, "lr": lr, "grad_norm": gnorm,
                       "total_loss": loss}
            metrics = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, axes), metrics)
            return new_state, metrics

    state_specs = {"params": P(),
                   "opt": {"m": P(axes), "v": P(axes), "master": P(axes),
                           "step": P()}}
    sm = jax.shard_map(step, mesh=mesh,
                       in_specs=(state_specs, P(axes)),
                       out_specs=(state_specs, P()),
                       check_vma=False)
    return jax.jit(sm, donate_argnums=(0,)), state_specs

