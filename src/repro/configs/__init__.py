"""Architecture registry + per-(arch × shape) input specs.

``ARCHS`` maps the assigned architecture ids to their config modules; every
module exports ``CONFIG`` (exact published numbers) and ``reduced()`` (tiny
same-family smoke variant). ``input_specs`` builds ShapeDtypeStruct stand-ins
for each cell — weak-type-correct, shardable, zero allocation — consumed by
``training.lower_cell``.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from repro.configs.base import (ModelConfig, OptimizerConfig, RunConfig,
                                ShapeConfig)

ARCHS: dict[str, str] = {
    "llava-next-34b": "repro.configs.llava_next_34b",
    "minitron-8b": "repro.configs.minitron_8b",
    "gemma-7b": "repro.configs.gemma_7b",
    "internlm2-1.8b": "repro.configs.internlm2_1_8b",
    "starcoder2-3b": "repro.configs.starcoder2_3b",
    "whisper-medium": "repro.configs.whisper_medium",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
    "rwkv6-7b": "repro.configs.rwkv6_7b",
    "kimi-k2-1t-a32b": "repro.configs.kimi_k2_1t_a32b",
    "granite-moe-3b-a800m": "repro.configs.granite_moe_3b_a800m",
}


def get_config(arch: str, reduced: bool = False) -> ModelConfig:
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ARCHS)}")
    mod = importlib.import_module(ARCHS[arch])
    return mod.reduced() if reduced else mod.CONFIG


def all_archs() -> list[str]:
    return list(ARCHS)


def input_specs(config: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStructs for one (arch × shape) cell.

    train/prefill: the token batch (+ modality-stub embeddings);
    decode: a single-token batch + the KV cache / recurrent state struct.
    """
    B, S = shape.global_batch, shape.seq_len
    f32 = jnp.dtype(config.dtype)
    i32 = jnp.int32

    def tok(b, s):
        return jax.ShapeDtypeStruct((b, s), i32)

    if shape.kind in ("train", "prefill"):
        if config.family == "vlm":
            n_img = config.num_image_tokens
            batch = {"tokens": tok(B, S - n_img),
                     "image_embeds": jax.ShapeDtypeStruct(
                         (B, n_img, config.d_model), f32)}
        elif config.family == "audio":
            batch = {"tokens": tok(B, S),
                     "frames": jax.ShapeDtypeStruct(
                         (B, config.encoder_seq, config.d_model), f32)}
        else:
            batch = {"tokens": tok(B, S)}
        return {"batch": batch}

    # decode: one new token against a seq_len-deep cache/state
    from repro.models.registry import get_model
    model = get_model(config)
    cache = jax.eval_shape(lambda: model.init_cache(config, B, S))
    return {"tokens": tok(B, 1), "cache": cache}


def batch_specs_logical(config: ModelConfig, shape: ShapeConfig) -> dict:
    """Logical sharding axes for the input batch (``lower_cell``'s
    in_shardings)."""
    if shape.kind in ("train", "prefill"):
        if config.family == "vlm":
            return {"batch": {"tokens": ("batch", "seq"),
                              "image_embeds": ("batch", "seq", "embed")}}
        if config.family == "audio":
            return {"batch": {"tokens": ("batch", "seq"),
                              "frames": ("batch", "frames", "embed")}}
        return {"batch": {"tokens": ("batch", "seq")}}
    from repro.models.registry import get_model
    model = get_model(config)
    return {"tokens": ("batch", "seq"),
            "cache": model.cache_specs(config)}


__all__ = [
    "ARCHS", "ModelConfig", "OptimizerConfig", "RunConfig", "ShapeConfig",
    "all_archs", "batch_specs_logical", "get_config", "input_specs",
]
