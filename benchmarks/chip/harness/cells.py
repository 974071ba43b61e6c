"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration
and traffic mix, and each per-layer metric. Each of those is a file of
its own under this directory, found by its name:

* a configuration: the file ``BENCHMARK.json`` gives it (``configs/``),
  whose fleet is one ``capacity`` for all ``machines`` or a list of
  ``machine_classes`` (name, count, capacity) in machine order;
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
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from gen.traffic import Traffic, load_traffic

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO = BENCH_DIR.parents[1]


@dataclass(frozen=True)
class MachineClass:
    """``count`` machines of one capacity."""

    name: str
    count: int
    capacity: Dict[str, float]


@dataclass(frozen=True)
class Config:
    """A deployment: its fleet as machine classes, in machine order."""

    name: str
    machines: int
    window_slots: int
    quanta: int
    preset: Optional[str]                 # a program preset it must equal
    classes: Tuple[MachineClass, ...]
    extra: Dict = field(default_factory=dict, compare=False)

    def __post_init__(self) -> None:
        if not self.classes:
            raise ValueError("machine_classes: no class")
        counted = sum(c.count for c in self.classes)
        if counted != self.machines:
            raise ValueError(f"machine_classes: counts sum to {counted}, "
                             f"not machines {self.machines}")
        first = set(self.classes[0].capacity)
        for c in self.classes:
            if set(c.capacity) != first:
                raise ValueError(
                    f"machine_classes: class {c.name!r} has resources "
                    f"{sorted(c.capacity)}, class {self.classes[0].name!r} "
                    f"{sorted(first)}")

    @property
    def resources(self) -> List[str]:
        return sorted(self.classes[0].capacity)

    def capacity_rows(self) -> List[Dict[str, float]]:
        """Each machine's capacity, in machine (class) order."""
        return [c.capacity for c in self.classes for _ in range(c.count)]

    def capacity_array(self) -> np.ndarray:
        """(machines, resources) capacities, resources sorted: the
        reference's capacity array."""
        res = self.resources
        return np.array([[row[r] for r in res] for row in self.capacity_rows()])


@dataclass(frozen=True)
class Cell:
    """One workload entry with the files it names."""

    spec: dict
    config: Config
    traffic: Traffic
    limits: dict


def load_benchmark(repo: Path = REPO) -> dict:
    return json.loads((repo / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def _capacity(where: str, raw) -> Dict[str, float]:
    if not isinstance(raw, dict) or not raw or any(
            isinstance(v, bool) or not isinstance(v, (int, float)) or v <= 0
            for v in raw.values()):
        raise ValueError(f"{where}: {raw!r} is not a map of resource to a "
                         "capacity > 0")
    return {k: float(v) for k, v in raw.items()}


def _classes(raw: dict) -> Tuple[MachineClass, ...]:
    if "capacity" in raw and "machine_classes" in raw:
        raise ValueError("give capacity or machine_classes, not both")
    if "capacity" in raw:
        return (MachineClass(raw["name"], int(raw["machines"]),
                             _capacity("capacity", raw["capacity"])),)
    if "machine_classes" not in raw:
        raise ValueError("missing capacity or machine_classes")
    out = []
    for i, c in enumerate(raw["machine_classes"]):
        where = f"machine_classes[{i}]"
        if not isinstance(c, dict) or set(c) != {"name", "count", "capacity"}:
            raise ValueError(f"{where}: {c!r} does not hold exactly name, "
                             "count and capacity")
        if isinstance(c["count"], bool) or not isinstance(c["count"], int) \
                or c["count"] < 1:
            raise ValueError(f"{where}: count {c['count']!r} is not a whole "
                             "number >= 1")
        out.append(MachineClass(str(c["name"]), c["count"],
                                _capacity(where + ".capacity", c["capacity"])))
    return tuple(out)


def load_config(path: Path) -> Config:
    raw = json.loads(Path(path).read_text())
    need = ("name", "machines", "window_slots", "quanta")
    missing = [k for k in need if k not in raw]
    if missing:
        raise ValueError(f"{path}: missing {missing}")
    try:
        return Config(name=raw["name"], machines=int(raw["machines"]),
                      window_slots=int(raw["window_slots"]),
                      quanta=int(raw["quanta"]), preset=raw.get("preset"),
                      classes=_classes(raw), extra=raw)
    except ValueError as e:
        raise ValueError(f"{path}: {e}") from None


def check_demands(cfg: Config, tr: Traffic, where: str) -> None:
    """A traffic may not demand a resource its configuration lacks."""
    lacking = sorted(tr.demand_resources() - set(cfg.resources))
    if lacking:
        raise ValueError(f"{where}: worker_demand/ps_demand name {lacking}, "
                         f"which config {cfg.name!r} lacks")


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


def load_cell(bench: dict, name: str, repo: Path = REPO) -> Cell:
    """The cell ``name`` with its configuration, traffic and limits, each
    checked as it loads."""
    spec = find_cell(bench, name)
    cfg = config_for(bench, spec, repo)
    tr = load_traffic(traffic_file(spec["traffic"]))
    check_demands(cfg, tr, f"traffic {spec['traffic']!r}")
    limits = json.loads((BENCH_DIR / "limits" / f"{name}.json").read_text())
    return Cell(spec, cfg, tr, limits)


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
