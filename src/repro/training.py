"""Train/serve step builders: model API × optimizer × GSPMD sharding.

``build_train_step``/``build_serve_fns`` produce the pure step functions;
``shardings_for``/``lower_*`` attach PartitionSpecs for a concrete mesh —
used identically by the real trainer (``launch/train.py``) and the streaming
pipeline (train-on-stream); ``lower_cell`` lowers the same functions for a
mesh without allocating.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import batch_specs_logical, input_specs
from repro.configs.base import ModelConfig, OptimizerConfig, ShapeConfig
from repro.models.registry import get_model
from repro.optim import adamw_update, init_opt_state, zero1_state_specs
from repro.parallel.sharding import (ShardingRules, tree_specs,
                                     tree_specs_shaped, use_mesh)
from repro.utils import get_logger

log = get_logger(__name__)


def rules_for(config: ModelConfig) -> ShardingRules:
    return ShardingRules(overrides=dict(config.sharding_overrides))


# -- step functions ------------------------------------------------------------
def build_train_step(config: ModelConfig, opt: OptimizerConfig
                     ) -> Callable[[dict, dict], tuple[dict, dict]]:
    model = get_model(config)

    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        def loss_fn(params):
            return model.loss_and_metrics(params, batch, config)

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        new_params, new_opt, opt_metrics = adamw_update(
            state["params"], grads, state["opt"], opt)
        return ({"params": new_params, "opt": new_opt},
                {**metrics, **opt_metrics, "total_loss": loss})

    return train_step


def build_serve_fns(config: ModelConfig):
    model = get_model(config)

    def prefill(params: dict, batch: dict) -> tuple[jax.Array, dict]:
        return model.prefill(params, batch, config)

    def decode_step(params: dict, tokens: jax.Array, cache: dict
                    ) -> tuple[jax.Array, dict]:
        return model.decode_step(params, tokens, cache, config)

    return prefill, decode_step


def init_state(key: jax.Array, config: ModelConfig,
               opt: OptimizerConfig) -> dict:
    model = get_model(config)
    params = model.init(key, config)
    state = {"params": params, "opt": init_opt_state(params, opt)}
    # Identical constant leaves (zeros/ones) can alias the same device
    # buffer, which breaks donation ("donate the same buffer twice") —
    # force-unique every leaf once at init.
    return jax.tree_util.tree_map(jnp.copy, state)


# -- sharding assembly -----------------------------------------------------------
@dataclass
class CellShardings:
    """All PartitionSpecs for one (arch × shape × mesh) cell."""
    mesh: Mesh
    rules: ShardingRules
    param_specs: Any
    state_specs: Any | None = None          # train
    batch_specs: Any | None = None
    cache_specs: Any | None = None          # decode

    def sharding(self, spec_tree: Any) -> Any:
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))


def shardings_for(config: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                  opt: OptimizerConfig | None = None) -> CellShardings:
    model = get_model(config)
    rules = rules_for(config)
    param_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), config))
    pspecs = tree_specs_shaped(model.param_specs(config), param_shapes,
                               mesh, rules)
    cell = CellShardings(mesh=mesh, rules=rules, param_specs=pspecs)
    bspec_logical = batch_specs_logical(config, shape)
    cell_inputs = input_specs(config, shape)
    if shape.kind == "train":
        if opt is None:
            opt = OptimizerConfig()
        cell.state_specs = {
            "params": pspecs,
            "opt": zero1_state_specs(pspecs, param_shapes, mesh, opt)}
        cell.batch_specs = tree_specs_shaped(
            bspec_logical["batch"], cell_inputs["batch"], mesh, rules)
    elif shape.kind == "prefill":
        cache_shapes = jax.eval_shape(
            lambda: model.init_cache(config, shape.global_batch,
                                     shape.seq_len))
        cell.batch_specs = tree_specs_shaped(
            bspec_logical["batch"], cell_inputs["batch"], mesh, rules)
        cell.cache_specs = tree_specs_shaped(
            model.cache_specs(config), cache_shapes, mesh, rules)
    else:  # decode
        cell.batch_specs = tree_specs_shaped(
            bspec_logical["tokens"], cell_inputs["tokens"], mesh, rules)
        cell.cache_specs = tree_specs_shaped(
            model.cache_specs(config), cell_inputs["cache"], mesh, rules)
    return cell


# -- lowering (no allocation) ------------------------------------------------
def lower_cell(config: ModelConfig, shape: ShapeConfig, mesh: Mesh,
               opt: OptimizerConfig | None = None):
    """Lower the cell's step function at full scale (no allocation).

    Returns (lowered, kind). train -> train_step(state, batch);
    prefill -> prefill(params, batch); decode -> decode_step(params, tokens,
    cache)."""
    opt = opt or OptimizerConfig()
    model = get_model(config)
    rules = rules_for(config)
    cell = shardings_for(config, shape, mesh, opt)
    specs = input_specs(config, shape)

    with use_mesh(mesh, rules):
        if shape.kind == "train":
            state_shapes = jax.eval_shape(
                lambda: init_state(jax.random.PRNGKey(0), config, opt))
            fn = build_train_step(config, opt)
            jitted = jax.jit(
                fn,
                in_shardings=(cell.sharding(cell.state_specs),
                              cell.sharding(cell.batch_specs)),
                out_shardings=(cell.sharding(cell.state_specs), None),
                donate_argnums=(0,))
            lowered = jitted.lower(state_shapes, specs["batch"])
        elif shape.kind == "prefill":
            param_shapes = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0), config))
            prefill, _ = build_serve_fns(config)
            jitted = jax.jit(
                prefill,
                in_shardings=(cell.sharding(cell.param_specs),
                              cell.sharding(cell.batch_specs)),
                out_shardings=(None, cell.sharding(cell.cache_specs)))
            lowered = jitted.lower(param_shapes, specs["batch"])
        else:  # decode
            param_shapes = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0), config))
            _, decode = build_serve_fns(config)
            jitted = jax.jit(
                decode,
                in_shardings=(cell.sharding(cell.param_specs),
                              cell.sharding(cell.batch_specs),
                              cell.sharding(cell.cache_specs)),
                out_shardings=(None, cell.sharding(cell.cache_specs)),
                donate_argnums=(2,))
            lowered = jitted.lower(param_shapes, specs["tokens"],
                                   specs["cache"])
    return lowered, shape.kind
