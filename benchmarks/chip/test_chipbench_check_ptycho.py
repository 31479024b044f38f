"""The ptychography cell's run, past the look for a chip, at a small size on
the CPU: a sound run is correct and compiles nothing in its window; a run
with the timed path broken underneath is not correct; and the control, the
plain reference in bfloat16, fails the configuration's limits where the
program passes them."""
import dataclasses
import time

import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from chipbench import device, harness, spec
from chipbench.apps import ptycho
from chipbench.compiles import CompileCounter

WORKLOAD = "ptycho-t2.scan"
CONFIG = dict(object_size=64, probe_size=16, scan_step=4, frames_per_scan=64,
              iterations_per_batch=2, refine_iterations=4)
TRAFFIC = dict(batch_frames=16, objects=2)


def _small_cell():
    cell = spec.find_cell(WORKLOAD)
    return dataclasses.replace(cell, config={**cell.config, **CONFIG},
                               traffic={**cell.traffic, **TRAFFIC})


@pytest.fixture
def small(monkeypatch, tmp_path):
    """``harness.run_cell`` on the CPU at the small size: no look for a
    chip, no compilation cache, output under ``tmp_path``."""
    cell = _small_cell()
    monkeypatch.setattr(spec, "find_cell", lambda name: cell)
    monkeypatch.setattr(device, "require_chips", lambda jax, chips: None)
    monkeypatch.setattr(harness, "enable_cache", lambda jax: None)
    monkeypatch.setattr(harness, "OUT_ROOT", str(tmp_path))

    def run(seed=2 ** 31 + 7):
        return harness.run_cell(WORKLOAD, seed, 0.0, False,
                                t_start=time.monotonic())
    return run


def test_sound_run_is_correct(small):
    r = small()
    assert r["correct"], r["checks"]
    assert r["attempted"] == 4 and r["failed"] == 0
    assert set(r["metrics"]) == {"scan_s", "batch_latency_s", "setup_s"}
    assert set(r["checks"]) == {
        "results_missing", "batches_misplaced", "first_batch_gap",
        "step_err_gap", "step_wave_gap", "step_probe_gap", "step_obj_gap"}
    assert list(r)[-1] == "checks"


def test_warm_up_leaves_nothing_to_compile(tmp_path):
    cell = _small_cell()
    app = ptycho.App(cell.config, cell.traffic, 5, str(tmp_path))
    app.setup()
    import jax
    counter = CompileCounter(jax)
    app.run_unit()
    app.close()
    assert counter.programs == 0


def _state_unchanged(step):
    def broken(psi, *args, **kw):
        return (psi,) + tuple(step(psi, *args, **kw)[1:])
    return broken


def _later_frames_altered(step):
    """The update of every frame past the first batch off by 0.1%."""
    def broken(psi, *args, **kw):
        new, *rest = step(psi, *args, **kw)
        scale = jnp.where(jnp.arange(len(new)) >= TRAFFIC["batch_frames"],
                          1.001, 1.0).astype(new.dtype)
        return (new * scale[:, None, None], *rest)
    return broken


def _probe_altered(step):
    def broken(*args, **kw):
        new, obj, probe, err = step(*args, **kw)
        return new, obj, probe * jnp.complex64(1.001), err
    return broken


def _answer_altered(step):
    def broken(*args, **kw):
        new, obj, probe, err = step(*args, **kw)
        return new, obj * jnp.complex64(1.01), probe, err
    return broken


@pytest.mark.parametrize("fault", [
    "state unchanged", "half of each batch left out", "answer altered",
    "later frames altered", "probe altered"])
def test_broken_timed_path_is_not_correct(small, monkeypatch, fault):
    step = {"state unchanged": _state_unchanged,
            "later frames altered": _later_frames_altered,
            "probe altered": _probe_altered,
            "answer altered": _answer_altered}.get(fault)
    if step:
        monkeypatch.setattr(ptycho, "raar_step", step(ptycho.raar_step))
    else:
        batch = ptycho.App._batch
        monkeypatch.setattr(ptycho.App, "_batch", lambda self, ids, info:
                            batch(self, ids[:len(ids) // 2], info))
    r = small()
    assert not r["correct"], (fault, r["checks"])


def test_control_fails_where_the_program_passes(tmp_path):
    cell = _small_cell()
    app = ptycho.App(cell.config, cell.traffic, 3, str(tmp_path))
    app.make_inputs()
    ctl = app.compare(app.control_outputs(0, ml_dtypes.bfloat16), 0)
    assert not all(c.ok for c in ctl), [(c.name, c.value) for c in ctl]
    same = app.compare(app.control_outputs(0, None), 0)
    assert all(c.ok for c in same), [(c.name, c.value) for c in same]
    assert all(np.isfinite(c.value) for c in ctl + same)
