"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration
and traffic mix, and each per-layer metric. Each of those is a file of
its own under this directory, found by its name:

* a configuration: the file ``BENCHMARK.json`` gives it (``configs/``);
* a traffic mix: ``traffic/<traffic>.json``;
* a per-layer metric: ``layer_metrics/<metric>.py``, a module whose
  ``read(ctx)`` returns the metric's value, or None when the run holds
  nothing for it to read.

A later cell or metric is a new file and a new entry; no code changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO = BENCH_DIR.parents[1]


@dataclass(frozen=True)
class Config:
    name: str
    machines: int
    window_slots: int
    quanta: int
    preset: str
    capacity: Dict[str, float]
    extra: Dict = field(default_factory=dict, compare=False)


def load_benchmark(repo: Path = REPO) -> dict:
    return json.loads((repo / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def load_config(path: Path) -> Config:
    raw = json.loads(Path(path).read_text())
    need = ("name", "machines", "window_slots", "quanta", "preset",
            "capacity")
    missing = [k for k in need if k not in raw]
    if missing:
        raise ValueError(f"{path}: missing {missing}")
    return Config(name=raw["name"], machines=int(raw["machines"]),
                  window_slots=int(raw["window_slots"]),
                  quanta=int(raw["quanta"]), preset=raw["preset"],
                  capacity={k: float(v) for k, v in raw["capacity"].items()},
                  extra=raw)


def config_for(bench: dict, cell: dict, repo: Path = REPO) -> Config:
    for c in bench["configs"]:
        if c["name"] == cell["config"]:
            cfg = load_config(repo / c["file"])
            if cfg.name != c["name"]:
                raise ValueError(f"{c['file']} names {cfg.name!r}, "
                                 f"not {c['name']!r}")
            return cfg
    raise KeyError(f"no config named {cell['config']!r}")


def traffic_file(name: str) -> Path:
    return BENCH_DIR / "traffic" / f"{name}.json"


def metric_reader(name: str) -> Callable[[dict], Optional[float]]:
    path = BENCH_DIR / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r}: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, kind: str) -> List[dict]:
    """The metrics of ``kind`` ("end_to_end" or "per_layer") this cell
    reports: those without a ``workloads`` list, and those that name it."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]
