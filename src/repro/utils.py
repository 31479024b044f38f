"""Shared utilities: mesh, compile cache and child-process helpers, logging,
timing, pytree helpers, numeric helpers."""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import math
import os
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

_LOG_FORMAT = "%(asctime)s %(levelname).1s %(name)s: %(message)s"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def make_mesh(shape, axes, **kwargs):
    """jax.make_mesh with every axis explicitly ``AxisType.Auto``."""
    kwargs.setdefault("axis_types", (jax.sharding.AxisType.Auto,) * len(axes))
    return jax.make_mesh(shape, axes, **kwargs)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here. Otherwise the cache lives at the fixed
    path ``<checkout>/.jax_cache``: a directory that moved between runs
    would never hit. Call it before the first compilation of the process.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # kernels and small step programs compile in well under JAX's default
    # one-second floor; cache them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


@contextlib.contextmanager
def cpu_only_children() -> Iterator[None]:
    """Processes started inside this block inherit ``JAX_PLATFORMS=cpu``.

    An accelerator belongs to one process at a time: data-plane children
    (detector producers, broker processes) started from a process that
    owns the chip must stay off it. Only start processes in the block; the
    parent's own JAX read its platform when it was imported.
    """
    old = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if old is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = old


def get_logger(name: str) -> logging.Logger:
    if not name.startswith("repro"):      # e.g. "__main__" under python -m
        name = f"repro.{name}"
    logger = logging.getLogger(name)
    if not logging.getLogger("repro").handlers:
        root = logging.getLogger("repro")
        handler = logging.StreamHandler()
        handler.setFormatter(logging.Formatter(_LOG_FORMAT))
        root.addHandler(handler)
        root.setLevel(os.environ.get("REPRO_LOG_LEVEL", "INFO"))
    return logger


@contextlib.contextmanager
def timed(label: str, sink: dict | None = None) -> Iterator[None]:
    """Context manager measuring wall time; optionally records into ``sink``."""
    t0 = time.perf_counter()
    yield
    dt = time.perf_counter() - t0
    if sink is not None:
        sink[label] = dt


def block_tree(tree: Any) -> Any:
    """Block until all arrays in a pytree are ready (for honest timing)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            leaf.block_until_ready()
    return tree


def tree_bytes(tree: Any) -> int:
    """Total byte size of all array leaves in a pytree."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "dtype") and hasattr(leaf, "shape"):
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def tree_params(tree: Any) -> int:
    """Total element count of all array leaves in a pytree."""
    return sum(
        int(np.prod(leaf.shape))
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "shape")
    )


def tree_any_nan(tree: Any) -> bool:
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            if bool(jnp.any(jnp.isnan(leaf))):
                return True
    return False


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} PiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b


def asdict_shallow(obj: Any) -> dict:
    """dataclasses.asdict without deep-copying array fields."""
    return {f.name: getattr(obj, f.name) for f in dataclasses.fields(obj)}
