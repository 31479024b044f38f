"""Partition-parallel ART reconstruction (paper §IV, Figs. 11-12).

The tilt series is *slicewise independent*: slices are partitioned across
workers (the paper repartitions the RDD so neighbouring slices share a
partition), each partition runs the ART row-action sweep (Pallas kernel) on
its slices, and the reconstructed sub-volumes are gathered for the
rendering stage (apps/tomo/render.py — the ParaView stage of Fig. 11).
"""
from __future__ import annotations

import threading
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps.tomo.projector import make_system, project
from repro.core.rdd import RDD
from repro.data.metrics import get_registry
from repro.kernels.art import kernel as art_kernel
from repro.kernels.art import ops as art_ops


@dataclass(frozen=True)
class TomoConfig:
    nray: int = 64
    angles: tuple = tuple(np.linspace(-75, 75, 25).tolist())
    beta: float = 1.0
    iterations: int = 2
    use_pallas: bool | None = None


def make_phantom(nslice: int, nray: int, seed: int = 0) -> np.ndarray:
    """Shepp-Logan-ish nested ellipsoids phantom volume."""
    rng = np.random.default_rng(seed)
    z, y, x = np.mgrid[:nslice, :nray, :nray].astype(np.float64)
    z = (z - nslice / 2) / (nslice / 2)
    y = (y - nray / 2) / (nray / 2)
    x = (x - nray / 2) / (nray / 2)
    vol = np.zeros((nslice, nray, nray))
    for _ in range(6):
        c = rng.uniform(-0.4, 0.4, 3)
        r = rng.uniform(0.15, 0.5, 3)
        a = rng.uniform(0.2, 1.0)
        mask = (((z - c[0]) / r[0]) ** 2 + ((y - c[1]) / r[1]) ** 2
                + ((x - c[2]) / r[2]) ** 2) < 1.0
        vol[mask] += a
    vol[((z**2 + y**2 + x**2) > 0.95)] = 0.0
    return vol.astype(np.float32)


def simulate_tilt_series(config: TomoConfig, nslice: int,
                         seed: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """Returns (volume_true, sinogram (Nslice, Nproj*Nray))."""
    vol = make_phantom(nslice, config.nray, seed)
    A = make_system(config.nray, np.asarray(config.angles))
    sino = project(A, vol)
    return vol, sino.astype(np.float32)


class SliceReconstructor:
    """The ART operator of one :class:`TomoConfig`: the dense system matrix
    ``A`` and its ``1/‖A_j‖²`` are placed on the device once, when the
    operator is made, and every call reconstructs a block of slices with
    the compiled sweep against them. A worker holds one operator for its
    whole stream, so no batch copies ``A`` again.

    ``placements`` and ``placed_bytes`` count the copies of ``A`` to the
    device (also as ``tomo_system_placements_total`` and
    ``tomo_system_bytes_total`` in the metrics registry). ``matrix_passes``
    counts the passes of ``A`` the calls launched, ``sweeps × ⌈k / k_blk⌉``
    for a block of k slices (also as ``tomo_matrix_passes_total``); each
    call runs inside a ``repro.tomo.sweep`` profiler span with its
    ``slices`` and ``passes``."""

    def __init__(self, config: TomoConfig) -> None:
        self.config = config
        n = config.nray
        A = make_system(n, np.asarray(config.angles))
        self.A = jax.device_put(A)
        self.inv_rip = jax.jit(art_ops.inverse_row_norms)(self.A)
        self.placements, self.placed_bytes = 1, A.nbytes
        reg = get_registry()
        reg.counter("tomo_system_placements_total",
                    help="copies of an ART system matrix to the device").inc()
        reg.counter("tomo_system_bytes_total",
                    help="bytes of ART system matrices copied to the "
                         "device").inc(A.nbytes)
        self.matrix_passes = 0
        self._passes = reg.counter(
            "tomo_matrix_passes_total",
            help="passes of an ART system matrix the sweeps launched")
        self._lock = threading.Lock()      # partitions call on many threads

        def run(A, inv_rip, blocks):
            with jax.named_scope("art/sweep"):
                f = art_ops.art_sweep_slices(
                    A, blocks, inv_rip, beta=config.beta,
                    iters=config.iterations, use_pallas=config.use_pallas)
            return f.reshape(-1, n, n)

        self._run = jax.jit(run)

    def __call__(self, sino_slices: np.ndarray) -> np.ndarray:
        """ART-reconstruct a block of slices (one RDD partition's work):
        (k, Nrow) -> (k, Nray, Nray)."""
        k = len(sino_slices)
        passes = art_kernel.matrix_passes(k, self.config.iterations)
        with jax.profiler.TraceAnnotation("repro.tomo.sweep", slices=k,
                                          passes=passes):
            blocks = jnp.asarray(sino_slices, jnp.float32)
            out = np.asarray(self._run(self.A, self.inv_rip, blocks))
        with self._lock:
            self.matrix_passes += passes
        self._passes.inc(passes)
        return out


def reconstruct_batch(rdd: RDD, operator: SliceReconstructor,
                      partitions: int) -> list[tuple[str, dict]] | None:
    """One micro-batch of the tilt series, as the paper's Fig. 11 runs it:
    the batch's ``(slice, row)`` records in slice order, ``parallelize``d
    into ``partitions`` of neighbouring slices, the operator mapped over
    each partition by the RDD scheduler, and one keyed sub-volume per
    partition (``slices-<first>-<last>``: the slice indices and their
    reconstruction). None for an empty batch."""
    records = sorted(rdd.collect(), key=lambda rec: rec[0])
    if not records:
        return None
    part = rdd.context.parallelize(records, min(partitions, len(records)))

    def sweep(items):
        idx = [i for i, _ in items]
        return idx, operator(np.stack([row for _, row in items]))

    return [(f"slices-{idx[0]:04d}-{idx[-1]:04d}",
             {"idx": np.asarray(idx, np.int64), "block": block})
            for idx, block in part.map_partitions(sweep).collect_partitions()]


def residual(volume: np.ndarray, sino: np.ndarray,
             config: TomoConfig) -> float:
    A = make_system(config.nray, np.asarray(config.angles))
    pred = project(A, volume)
    return float(np.linalg.norm(pred - sino) / (np.linalg.norm(sino) + 1e-12))
