"""Application-level behaviour: ptycho RAAR convergence, tomo ART, and the
streaming pipelines end-to-end (paper §III/§IV)."""
import os
import re
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.ptycho.sim import (gather_patches, scatter_add_patches,
                                   simulate)
from repro.apps.ptycho.solver import (SolverConfig, overlap_update,
                                      raar_step, reconstruct,
                                      reconstruction_quality, init_waves)
from repro.apps.tomo.solver import (SliceReconstructor, TomoConfig, residual,
                                    simulate_tilt_series)
from repro.core import (Broker, Context, NearRealTimePipeline,
                        PipelineConfig)


def test_gather_scatter_adjoint():
    """<scatter(x), y> == <x, gather(y)> — the adjoint pair used by eqs 4-5."""
    key = jax.random.PRNGKey(0)
    obj = jax.random.normal(key, (16, 16))
    pos = np.array([[0, 0], [4, 7], [9, 9]], np.int32)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 6, 6))
    scat = scatter_add_patches(jnp.zeros((16, 16)), pos, x)
    gath = gather_patches(obj, pos, 6)
    lhs = float(jnp.sum(scat * obj))
    rhs = float(jnp.sum(x * gath))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-5)


def test_overlap_update_recovers_object_from_true_waves():
    """Given the TRUE exit waves, eq. (4) recovers the object on the scanned
    region (up to probe coverage)."""
    prob = simulate(obj_size=64, probe_size=24, step=6)
    patches = gather_patches(prob.object_true, jnp.asarray(prob.positions),
                             24)
    psi_true = prob.probe_true[None] * patches
    obj, _ = overlap_update(psi_true, jnp.asarray(prob.positions),
                            prob.probe_true, (64, 64), update_probe=False,
                            use_pallas=False)
    m = 16
    got = np.asarray(obj)[m:-m, m:-m]
    want = np.asarray(prob.object_true)[m:-m, m:-m]
    np.testing.assert_allclose(np.abs(got), np.abs(want), rtol=0.1, atol=0.1)


def test_raar_reconstruction_converges():
    prob = simulate(obj_size=96, probe_size=32, step=8)
    cfg = SolverConfig(iterations=50, use_pallas=False)
    out = reconstruct(prob, cfg)
    errs = np.asarray(out["errors"])
    assert errs[-1] < 0.35 * errs[0]
    q = reconstruction_quality(out["object"], prob.object_true, margin=16)
    assert q > 0.9, q


def test_raar_with_pallas_kernels_matches_ref_path():
    """One RAAR step with Pallas kernels (interpret) == pure-jnp path."""
    prob = simulate(obj_size=48, probe_size=16, step=6)
    pos = jnp.asarray(prob.positions)
    cfg_ref = SolverConfig(use_pallas=False)
    cfg_pl = SolverConfig(use_pallas=True)
    psi = init_waves(prob.magnitudes, prob.probe_true)
    a = raar_step(psi, prob.magnitudes, pos, prob.probe_true, (48, 48),
                  cfg_ref, 5)
    b = raar_step(psi, prob.magnitudes, pos, prob.probe_true, (48, 48),
                  cfg_pl, 5)
    for x, y in zip(a[:3], b[:3]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-4, atol=2e-4)


def test_tomo_art_reduces_residual():
    cfg = TomoConfig(nray=32, angles=tuple(np.linspace(-75, 75, 19).tolist()),
                     iterations=3, use_pallas=False)
    vol, sino = simulate_tilt_series(cfg, nslice=6)
    rec = SliceReconstructor(cfg)(sino)
    r = residual(rec, sino, cfg)
    assert r < 0.3, r                      # limited-angle ART: large drop
    err = np.linalg.norm(rec - vol) / np.linalg.norm(vol)
    assert err < 0.6, err


def test_near_realtime_pipeline_end_to_end():
    """Producer thread -> broker -> micro-batches -> process -> report."""
    broker = Broker()
    broker.create_topic("frames", partitions=2)
    done = threading.Event()

    def producer():
        for i in range(40):
            broker.produce("frames", float(i), partition=i % 2)
        done.set()

    sums = []

    def process(rdd, info, bridge):
        vals = rdd.collect()
        sums.append(sum(vals))
        return sums[-1]

    pipe = NearRealTimePipeline(
        broker, PipelineConfig(topics=["frames"], batch_interval=0.02,
                               max_records_per_partition=5),
        process)
    threading.Thread(target=producer, daemon=True).start()
    report = pipe.run_until_drained(lambda: done.is_set())
    assert report.records == 40
    assert sum(sums) == sum(range(40))
    assert report.batches >= 4
    assert report.mean_latency < 0.5


def test_ptycho_example_prints_each_batch_processing_time(tmp_path):
    """The example's per-batch line reads the batch's processing time once
    the stream has stamped it, not the 0.00 it holds inside the batch
    function."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "examples/ptycho_pipeline.py", "--fast", "--out",
         str(tmp_path / "out")], cwd=root, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    procs = [float(t) for t in re.findall(r"^  batch \d+: .* proc ([\d.]+)s$",
                                          out.stdout, re.M)]
    assert len(procs) == 4 and min(procs) > 0, out.stdout
