"""Streaming tomography against a plain reference, on the CPU.

The tilt series streams through ``NearRealTimePipeline`` as the example runs
it: a ``ProjectionSource`` of ``(slice, row)`` records, micro-batches of
neighbouring slices, ``reconstruct_batch`` over the RDD partitions with one
``SliceReconstructor`` that holds the system matrix on the device, and
keyed sub-volumes in an NPZ sink. What the sink published is compared with
a float64 NumPy Kaczmarz sweep written from the algorithm (paper Fig. 12):
row at a time, in the kernel's row order, from ``f0 = 0``, with no JAX.
The vectorised system matrix is compared with the ray-at-a-time loop it
replaced.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.apps.tomo.projector import (make_system, parallel_ray_matrix,
                                       project)
from repro.apps.tomo.solver import (SliceReconstructor, TomoConfig,
                                    make_phantom, reconstruct_batch)
from repro.core import Broker, Context, NearRealTimePipeline, PipelineConfig
from repro.core.rdd import TaskScheduler
from repro.data import NpzDirectorySink, ProjectionSource
from repro.data.metrics import MetricsRegistry, set_registry

NRAY, ANGLES, SLICES, BATCH, PARTITIONS = 24, 7, 12, 4, 2
# float32 against float64: every one of the 2 x 168 row updates rounds the
# image at float32's unit roundoff (6e-8) and the Kaczmarz map, a product
# of projections, does not amplify it: both paths read 1.1e-7 to 2.4e-7
# here. A sweep rounded to bfloat16 (unit roundoff 4e-3) reads 6.9e-3 to
# 9.2e-3, so 1e-4 lies 400 times above the one and 70 times below the other.
SLICE_GAP = 1e-4


def loop_ray_matrix(nray: int, angles, dtype=np.float32) -> np.ndarray:
    """The parallel-ray matrix one ray at a time, as the projector built it
    before it was vectorised: the oracle of the vectorised build."""
    n = nray
    nsamp = 2 * n
    ts = np.linspace(-n / 2, n / 2, nsamp)
    offs = np.arange(n) - n / 2 + 0.5
    A = np.zeros((len(angles) * n, n * n), dtype=dtype)
    step = ts[1] - ts[0]
    for ai, theta in enumerate(np.deg2rad(np.asarray(angles, np.float64))):
        d = np.array([np.cos(theta), np.sin(theta)])
        o = np.array([-np.sin(theta), np.cos(theta)])
        for ri, r in enumerate(offs):
            pts = r * o[None, :] + ts[:, None] * d[None, :] + n / 2 - 0.5
            ys, xs = pts[:, 0], pts[:, 1]
            y0 = np.floor(ys).astype(int)
            x0 = np.floor(xs).astype(int)
            fy, fx = ys - y0, xs - x0
            row = np.zeros(n * n, dtype=dtype)
            for dy, dx, wgt in ((0, 0, (1 - fy) * (1 - fx)),
                                (0, 1, (1 - fy) * fx),
                                (1, 0, fy * (1 - fx)),
                                (1, 1, fy * fx)):
                yy, xx = y0 + dy, x0 + dx
                ok = (yy >= 0) & (yy < n) & (xx >= 0) & (xx < n)
                np.add.at(row, (yy[ok] * n + xx[ok]),
                          (wgt[ok] * step).astype(dtype))
            A[ai * n + ri] = row
    return A


def kaczmarz(A: np.ndarray, b: np.ndarray, beta: float, sweeps: int
             ) -> np.ndarray:
    """ART from ``f = 0``: for each sweep, for each row j in order,
    ``f += β (b_j − ⟨A_j, f⟩) / ‖A_j‖² · A_j``, skipping empty rows."""
    f = np.zeros(A.shape[1])
    rip = np.einsum("ij,ij->i", A, A)
    for _ in range(sweeps):
        for j in range(A.shape[0]):
            if rip[j] > 0:
                f += beta * (b[j] - A[j] @ f) / rip[j] * A[j]
    return f


def _rel(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


ANGLE_SETS = {"limited": np.linspace(-75, 75, 25),
              "half-turn": np.linspace(0, 180, 7, endpoint=False)}


@pytest.mark.parametrize("nray", [16, 32])
@pytest.mark.parametrize("angles", sorted(ANGLE_SETS))
def test_parallel_ray_matrix_matches_the_ray_loop(nray, angles):
    ang = ANGLE_SETS[angles]
    want = loop_ray_matrix(nray, ang)
    got = parallel_ray_matrix.__wrapped__(nray, tuple(ang.tolist()))
    assert got.shape == want.shape and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())


def _config(use_pallas) -> TomoConfig:
    return TomoConfig(nray=NRAY,
                      angles=tuple(np.linspace(-75, 75, ANGLES).tolist()),
                      iterations=2, beta=1.0, use_pallas=use_pallas)


def _stream(cfg, sino, out_dir, operator=None):
    """The tilt series through the pipeline; the published slices by index
    and the keys, batch by batch."""
    operator = operator or SliceReconstructor(cfg)
    sink = NpzDirectorySink(str(out_dir))
    ctx = Context(scheduler=TaskScheduler(num_executors=PARTITIONS))
    keys = []

    def process(rdd, info, bridge):
        out = reconstruct_batch(rdd, operator, PARTITIONS)
        keys.append([k for k, _ in out])
        return out

    pipe = NearRealTimePipeline(
        Broker(), PipelineConfig(batch_interval=0.001,
                                 max_records_per_partition=BATCH),
        process, context=ctx, sinks=[sink])
    pipe.subscribe_source(ProjectionSource(sino), topic="tilt-series")
    pipe.run(max_batches=len(sino) // BATCH)
    pipe.close()
    published = {}
    for key in sink.keys_on_disk():
        with np.load(sink.path_for(key)) as z:
            for i, img in zip(z["idx"], z["block"]):
                published[int(i)] = img
    return published, keys, operator


@pytest.fixture(scope="module")
def series():
    """Two seeded phantoms' tilt series, and the float64 system matrix."""
    cfg = _config(None)
    A = make_system(NRAY, np.asarray(cfg.angles))
    sinos = [project(A, make_phantom(SLICES, NRAY, seed)).astype(np.float32)
             for seed in (3, 2 ** 31 + 11)]
    return sinos, loop_ray_matrix(NRAY, cfg.angles, np.float64)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["reference path", "kernel, interpreted"])
@pytest.mark.parametrize("phantom", [0, 1])
def test_published_slices_match_the_float64_reference(
        series, tmp_path, use_pallas, phantom):
    sinos, A64 = series
    cfg = _config(use_pallas)
    sino = sinos[phantom]
    published, keys, _ = _stream(cfg, sino, tmp_path)
    assert sorted(published) == list(range(SLICES))
    # each batch: PARTITIONS sub-volumes of neighbouring slices
    per = BATCH // PARTITIONS
    assert keys == [[f"slices-{b + p * per:04d}-{b + p * per + per - 1:04d}"
                     for p in range(PARTITIONS)]
                    for b in range(0, SLICES, BATCH)]
    gaps = []
    for s in range(SLICES):
        want = kaczmarz(A64, sino[s].astype(np.float64), cfg.beta,
                        cfg.iterations)
        if not want.any():          # a slice past the phantom's support
            assert not published[s].any()
            continue
        gaps.append(_rel(published[s].ravel(), want))
    assert len(gaps) >= SLICES // 2 and max(gaps) < SLICE_GAP, gaps


def test_a_bfloat16_sweep_fails_the_tolerance(series):
    """The tolerance can see a precision below the configuration's: the
    same sweep with the image rounded to bfloat16 after every row fails."""
    import ml_dtypes
    sinos, A64 = series
    b = sinos[0][SLICES // 2].astype(np.float64)
    want = kaczmarz(A64, b, 1.0, 2)

    def bf16(x):
        return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)

    A = bf16(A64)
    f = np.zeros(A.shape[1])
    rip = np.einsum("ij,ij->i", A, A)
    for _ in range(2):
        for j in range(A.shape[0]):
            if rip[j] > 0:
                f = bf16(f + (b[j] - A[j] @ f) / rip[j] * A[j])
    assert _rel(f, want) > 10 * SLICE_GAP


def test_several_batches_place_the_system_once(series, tmp_path):
    registry = MetricsRegistry()
    previous = set_registry(registry)
    try:
        published, keys, operator = _stream(_config(None), series[0][0],
                                            tmp_path)
    finally:
        set_registry(previous)
    assert len(keys) == SLICES // BATCH == 3
    assert operator.placements == 1
    assert operator.placed_bytes == ANGLES * NRAY * NRAY * NRAY * 4
    snap = {m.name: m.value() for m in registry.metrics()}
    assert snap["tomo_system_placements_total"] == 1
    assert snap["tomo_system_bytes_total"] == operator.placed_bytes


def test_each_partition_sweep_is_counted_and_spanned(series, tmp_path):
    """Each partition's call makes ``sweeps × ⌈k / k_blk⌉`` passes of
    ``A``: here 2 slices in one block, 2 sweeps. The operator, the
    registry's ``tomo_matrix_passes_total`` and the ``repro.tomo.sweep``
    spans of 3 streamed batches all count them."""
    registry = MetricsRegistry()
    previous = set_registry(registry)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        _, keys, operator = _stream(_config(None), series[0][0],
                                    tmp_path / "sink")
    finally:
        jax.profiler.stop_trace()
        set_registry(previous)
    per = BATCH // PARTITIONS
    assert len(keys) == 3 and all(len(k) == PARTITIONS for k in keys)
    calls = 3 * PARTITIONS
    assert operator.matrix_passes == calls * 2
    snap = {m.name: m.value() for m in registry.metrics()}
    assert snap["tomo_matrix_passes_total"] == calls * 2
    [path] = glob.glob(os.path.join(tmp_path, "trace", "**", "*.xplane.pb"),
                       recursive=True)
    spans = [dict(ev.stats)
             for plane in jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "repro.tomo.sweep"]
    assert [(int(s["slices"]), int(s["passes"])) for s in spans] == \
        [(per, 2)] * calls


def test_the_sweep_is_named_for_the_trace():
    """``art/sweep`` reaches the compiled program's op metadata, where a
    device trace reads it as each op's ``tf_op``."""
    import jax.numpy as jnp
    op = SliceReconstructor(_config(None))
    blocks = jnp.zeros((2, ANGLES * NRAY), jnp.float32)
    text = op._run.lower(op.A, op.inv_rip, blocks).as_text(debug_info=True)
    assert "art/sweep" in text
