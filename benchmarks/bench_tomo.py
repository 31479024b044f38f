"""Paper Fig. 16: ART reconstruction time vs workers (+ the 6×-over-TomViz
claim).

Measured: (a) a TomViz-style pure-NumPy row loop (the paper's baseline),
(b) our jitted ART kernel path, both on one slice — the single-worker
speedup reproduces the paper's '6x improvement' claim class. Worker scaling
is measured through the RDD scheduler at 1/2/4 partitions (thread executors
on 1 core — scaling is derived for the TPU model where slices are
embarrassingly parallel, paper Fig. 16 shape).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import HBM_BW, emit, time_call


def tomviz_art(A: np.ndarray, b: np.ndarray, iters: int = 1,
               beta: float = 1.0) -> np.ndarray:
    """Paper Fig. 12 pseudocode, faithfully row-by-row in NumPy."""
    nrow, ncol = A.shape
    f = np.zeros(ncol, np.float32)
    rip = (A * A).sum(1)
    for _ in range(iters):
        for j in range(nrow):
            row = A[j]
            a = (b[j] - row @ f) / max(rip[j], 1e-12)
            f = f + row * a * beta
    return f


def run(nray: int = 32, angles: int = 19, nslice: int = 8) -> None:
    from repro.apps.tomo.projector import make_system
    from repro.apps.tomo.solver import (SliceReconstructor, TomoConfig,
                                        simulate_tilt_series)

    cfg = TomoConfig(nray=nray,
                     angles=tuple(np.linspace(-75, 75, angles).tolist()),
                     iterations=1)
    vol, sino = simulate_tilt_series(cfg, nslice)
    A = make_system(nray, np.asarray(cfg.angles))

    t_tomviz = time_call(lambda: tomviz_art(A, sino[0]), repeats=3)
    emit("tomo/tomviz_numpy_slice", t_tomviz,
         f"measured: {angles * nray} rows x {nray}^2, pure numpy")

    operator = SliceReconstructor(cfg)
    operator(sino[:1])  # compile
    t_ours = time_call(lambda: operator(sino[:1]), repeats=3)
    emit("tomo/art_jax_slice", t_ours,
         f"measured: same slice, jitted ART; speedup x{t_tomviz / t_ours:.1f}"
         f" (paper claims 6x over TomViz)")

    for workers in (1, 2, 4):
        from repro.core import Context
        from repro.core.rdd import TaskScheduler
        ctx = Context(scheduler=TaskScheduler(num_executors=workers,
                                              speculation=False))
        rdd = ctx.parallelize([(i, sino[i]) for i in range(nslice)], workers)

        def job():
            rdd.map_partitions(
                lambda items: operator(
                    np.stack([b for _, b in items]))).collect_partitions()

        t = time_call(job, repeats=2)
        # embarrassingly parallel on real hardware: derived = t1 / workers
        emit(f"tomo/art_{workers}workers", t,
             f"measured on 1 core; ideal-scaling model: "
             f"{t_ours * nslice / workers:.4f}s")


if __name__ == "__main__":
    run()
