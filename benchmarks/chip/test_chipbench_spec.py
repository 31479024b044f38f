"""CPU tests of the benchmark's pieces that need no run: finding cells and
metrics by name, the contract of ``BENCHMARK.json``, the peaks table, the
operation and byte counts, the input generators, and the command's refusal
to run without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import device, roofline, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_spec()


def test_finds_cell_config_traffic_and_metrics_by_name():
    cell = spec.find_cell("ptycho-t2.scan")
    assert cell.config["app"] == "ptycho" and cell.chips == 1
    assert cell.traffic["batch_frames"] == 64
    e2e = {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "batch_latency_s", "scan_s"} == e2e
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        spec.find_cell("no-such.cell")


def test_benchmark_json_keeps_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    configs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
        assert os.path.exists(os.path.join(spec.CHECKOUT, c["file"]))
    for w in bench["workloads"]:
        assert w["config"] in configs and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "traffic",
                                           w["traffic"] + ".json"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["workloads"]
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "metrics",
                                           m["name"] + ".py"))
    assert e2e["setup_s"]["bound"] <= 0.25


def test_unknown_device_kind_is_refused():
    assert device.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        device.peaks_for("TPU v99")


def test_operation_and_byte_counts_match_hand_counts():
    # 2 frames of 4x4 on an 8x8 object: 32 elements; FFTs 2·5·32·log2(16)
    flops, nbytes = roofline.raar_iteration(2, 4, (8, 8))
    assert flops == 2 * 5 * 32 * 4 + 54 * 32
    assert nbytes == 32 * 20 + 2 * 64 * 8 + 2 * 16 * 8 + 2 * 2 * 4
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline.least_time(1000, 50, peaks) == (10.0, "compute")
    assert roofline.least_time(10, 50, peaks) == (5.0, "memory")


SMALL = (dict(object_size=64, probe_size=16, scan_step=4, frames_per_scan=64),
         dict(batch_frames=16, objects=2))


def _inputs(seed, tmp_path):
    from chipbench import harness
    cell = spec.find_cell("ptycho-t2.scan")
    cfg = {**cell.config, **SMALL[0]}
    app = harness.load_app(cfg).App(cfg, {**cell.traffic, **SMALL[1]}, seed,
                                    str(tmp_path))
    app.make_inputs()
    return [app.positions, app.mags_host]


def test_generators_repeat_per_seed(tmp_path):
    big = 2 ** 31 + 12345          # seeds beyond 32 signed bits
    a = _inputs(big, tmp_path)
    b = _inputs(big, tmp_path)
    c = _inputs(big + 1, tmp_path)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not all(np.array_equal(x, z) for x, z in zip(a, c))
    assert all(np.all(np.isfinite(x)) for x in a)


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "chip", "run.py"),
         "--workload", "ptycho-t2.scan", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_run_refuses_a_machine_without_a_chip():
    p = _run(spec.CHECKOUT)
    assert p.returncode != 0 and _no_result(p.stdout)
    assert "no chip" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    """Past the look for a chip, a run in a directory that holds only
    ``BENCHMARK.json`` and the benchmark's files fails and prints no
    result: the program under test is not there."""
    shutil.copy(spec.SPEC_FILE, tmp_path / "BENCHMARK.json")
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(spec.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, sys.argv.pop(1)); "
            "from chipbench import device; "
            "device.require_chips = lambda jax, chips: None; "
            "import run; sys.exit(run.main(sys.argv[1:]))")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    p = subprocess.run(
        [sys.executable, "-c", code, str(bench), "--workload",
         "ptycho-t2.scan", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and _no_result(p.stdout)
    assert "No module named 'repro'" in p.stderr


def test_result_line_has_checks_last():
    from chipbench import harness
    result = {"correct": True, "attempted": 1, "failed": 0, "metrics": {},
              "device": {}, "checks": {"gap": {"value": 0.1, "limit": 1.0}}}
    assert list(json.loads(json.dumps(result)))[-1] == "checks"
    assert harness.Check("gap", 0.1, 1.0).ok
    assert not harness.Check("gap", float("inf"), 1.0).ok
