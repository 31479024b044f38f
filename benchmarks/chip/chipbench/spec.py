"""Finds a cell, its configuration, its traffic mix and its metrics by the
names ``BENCHMARK.json`` gives them."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Callable

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(BENCH_DIR))
SPEC_FILE = os.path.join(CHECKOUT, "BENCHMARK.json")


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict[str, Any]
    traffic_name: str
    traffic: dict[str, Any]
    end_to_end: list[dict[str, Any]]
    per_layer: list[dict[str, Any]]


def _load_json(path: str) -> dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict[str, Any], workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load_spec(path: str = SPEC_FILE) -> dict[str, Any]:
    return _load_json(path)


def find_cell(workload: str, spec: dict[str, Any] | None = None) -> Cell:
    """The cell named ``workload``, with its configuration and traffic
    loaded from their files; ``KeyError`` for an unknown name."""
    spec = spec or load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = configs[w["config"]]
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if m["moves"] in moved and _applies(m, workload)]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=cfg["name"],
        config=_load_json(os.path.join(CHECKOUT, cfg["file"])),
        traffic_name=w["traffic"],
        traffic=_load_json(os.path.join(BENCH_DIR, "traffic",
                                        f"{w['traffic']}.json")),
        end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str) -> Callable[[Any], float | None]:
    """``read(run)`` of ``metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    mod_name = "chipbench_metric_" + name.replace(".", "_").replace("-", "_")
    module_spec = importlib.util.spec_from_file_location(mod_name, path)
    if module_spec is None or module_spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read
