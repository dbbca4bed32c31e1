"""Finding a cell's parts by name.

``BENCHMARK.json`` at the checkout's root lists the cells, the end-to-end
metrics and the per-layer metrics. A cell names a configuration, which is
``bench/configs/<config>.json``, and a traffic mix, which is
``bench/traffic/<traffic>.json``. A per-layer metric ``<name>`` is read by
``bench/metrics/<name>.py``. Adding a cell takes new files and new entries
only; an unknown name is an error.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CONFIG_DIR = BENCH_DIR / "configs"
METRIC_DIR = BENCH_DIR / "metrics"


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")


def load_config(name: str) -> dict:
    path = CONFIG_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} ({path})")
    conf = json.loads(path.read_text())
    if conf.get("name") != name:
        raise ValueError(f"{path} names itself {conf.get('name')!r}")
    return conf


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def end_to_end(bench: dict, workload: str) -> List[dict]:
    return [m for m in bench["end_to_end"] if _applies(m, workload)]


def per_layer(bench: dict, workload: str) -> List[dict]:
    return [m for m in bench["per_layer"] if _applies(m, workload)]


def load_metric(name: str):
    """The reader module of per-layer metric ``name``."""
    path = METRIC_DIR / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
