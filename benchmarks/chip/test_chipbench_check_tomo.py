"""The tomography cell's run, past the look for a chip, at a small size on
the CPU: a sound run is correct, compiles nothing in its window and places
the system matrix in set-up only; a run with the timed path broken
underneath is not correct; the control, the plain reference in bfloat16,
fails the configuration's limit where the program passes it; and the
cell's per-layer readers give nothing, without raising, for a run with no
trace."""
import dataclasses
import time

import ml_dtypes
import numpy as np
import pytest

from chipbench import device, harness, roofline, roofline_art, spec
from chipbench.apps import tomo
from chipbench.compiles import CompileCounter

WORKLOAD = "tomo-art256.volume"
CONFIG = dict(nray=16, slices_per_volume=16,
              angles_deg={"first": -75.0, "last": 75.0, "count": 5})
TRAFFIC = dict(batch_slices=8, partitions=2, volumes=2)
READERS = ("art_slice_ms", "art_roofline", "rdd_idle_ms",
           "rdd_speculative_per_batch", "idle_pct.tomo")


def _small_cell():
    cell = spec.find_cell(WORKLOAD)
    return dataclasses.replace(cell, config={**cell.config, **CONFIG},
                               traffic={**cell.traffic, **TRAFFIC})


@pytest.fixture
def small(monkeypatch, tmp_path):
    """``harness.run_cell`` on the CPU at the small size: no look for a
    chip, no compilation cache, output under ``tmp_path``; a window of
    ``seconds`` holds whole micro-batches."""
    cell = _small_cell()
    monkeypatch.setattr(spec, "find_cell", lambda name: cell)
    monkeypatch.setattr(device, "require_chips", lambda jax, chips: None)
    monkeypatch.setattr(harness, "enable_cache", lambda jax: None)
    monkeypatch.setattr(harness, "OUT_ROOT", str(tmp_path))

    def run(seed=2 ** 31 + 7, seconds=0.5):
        return harness.run_cell(WORKLOAD, seed, seconds, False,
                                t_start=time.monotonic())
    return run


def test_sound_run_is_correct(small):
    r = small()
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert set(r["metrics"]) == {"batch_latency_s", "setup_s"}
    assert set(r["checks"]) == {"results_missing", "batches_misplaced",
                                "slice_gap"}
    assert r["checks"]["slice_gap"]["value"] < 1e-5
    assert list(r)[-1] == "checks"


def test_cell_reports_its_metrics_and_not_the_scan():
    cell = spec.find_cell(WORKLOAD)
    assert cell.config["app"] == "tomo" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"batch_latency_s",
                                                   "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(READERS) | {
        "art_system_uploads"}
    for m in cell.per_layer:
        assert m["moves"] == "batch_latency_s"
        assert callable(spec.metric_reader(m["name"]))


def test_window_places_nothing_and_compiles_nothing(tmp_path):
    cell = _small_cell()
    app = tomo.App(cell.config, cell.traffic, 5, str(tmp_path))
    app.setup()
    import jax
    counter = CompileCounter(jax)
    for _ in range(3):
        app.run_unit()
    app.close()
    assert counter.programs == 0
    w = harness.Window(app.units[1]["start"], app.units[-1]["end"], 0, 0)
    facts = app.facts(w)
    assert facts["batches"] == 3 and facts["system_uploads"] == 0
    assert facts["slices"] == 3 * TRAFFIC["batch_slices"]
    # each batch: its own slices, in partitions of neighbouring ones
    firsts = [b["first"] for b in app.batches]
    assert firsts == [8 * k for k in range(4)]
    assert app.batches[1]["keys"] == ["slices-0008-0011", "slices-0012-0015"]


def _scaled(operator_call):
    """Every slice's image 1% off."""
    def broken(self, sino):
        return operator_call(self, sino) * np.float32(1.01)
    return broken


@pytest.mark.parametrize("fault", ["answer altered", "partition dropped",
                                   "one sweep"])
def test_broken_timed_path_is_not_correct(small, monkeypatch, fault):
    if fault == "answer altered":
        monkeypatch.setattr(tomo.SliceReconstructor, "__call__",
                            _scaled(tomo.SliceReconstructor.__call__))
    elif fault == "partition dropped":
        batch = tomo.reconstruct_batch
        monkeypatch.setattr(tomo, "reconstruct_batch",
                            lambda *a: batch(*a)[:-1])
    else:
        config = tomo.TomoConfig
        monkeypatch.setattr(tomo, "TomoConfig", lambda **kw: config(
            **{**kw, "iterations": 1}))
    r = small()
    assert not r["correct"], (fault, r["checks"])


def test_control_fails_where_the_program_passes(tmp_path):
    cell = _small_cell()
    app = tomo.App(cell.config, cell.traffic, 3, str(tmp_path))
    app.make_inputs()
    ctl = app.compare(app.control_outputs(0, ml_dtypes.bfloat16), 0)
    assert not all(c.ok for c in ctl), [(c.name, c.value) for c in ctl]
    same = app.compare(app.control_outputs(0, None), 0)
    assert all(c.ok for c in same), [(c.name, c.value) for c in same]
    assert all(np.isfinite(c.value) for c in ctl + same)


def test_generators_repeat_per_seed(tmp_path):
    cell = _small_cell()

    def sino(seed):
        app = tomo.App(cell.config, cell.traffic, seed, str(tmp_path))
        app.make_inputs()
        return app.sino

    big = 2 ** 31 + 12345          # seeds beyond 32 signed bits
    a, b, c = sino(big), sino(big), sino(big + 1)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.all(np.isfinite(a)) and a.shape == (2 * 16, 5 * 16)


def test_art_counts_match_hand_counts():
    # 2 slices, 1 sweep, 8 rows of 4 columns
    flops, nbytes = roofline_art.art_batch(2, 8, 4, 1)
    assert flops == 4 * 8 * 4 * 2
    assert nbytes == (8 * 4 + 2 * 8 + 2 * 4) * 4
    # the cell: A read twice (3.36 GB) dominates, 4.1 ms at 819 GB/s
    t, bound = roofline.least_time(*roofline_art.art_batch(64, 6400, 65536, 2),
                                   device.peaks_for("TPU v5 lite"))
    assert bound == "memory" and 4.0e-3 < t < 4.2e-3


def test_readers_give_nothing_without_a_trace():
    run = harness.Run(WORKLOAD, {}, {}, harness.Window(0, 1, 0, 1), 0,
                      device.peaks_for("TPU v5 lite"), None,
                      {"batches": 2, "slices": 128, "system_uploads": 0,
                       "art_work": (1.0, 1.0)})
    for name in READERS:
        assert spec.metric_reader(name)(run) is None, name
    assert spec.metric_reader("art_system_uploads")(run) == 0
