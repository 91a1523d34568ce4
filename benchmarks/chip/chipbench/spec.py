"""Where the benchmark's data lives, found by name.

`BENCHMARK.json` at the checkout's root names the cells, the metrics
and the configurations. Everything that belongs to one of them is a
file of its own under `benchmarks/chip/`:

    configs/<config>.json     the configuration as it is run
    traffic/<mix>.json        the parameters of one traffic mix
    metrics/<metric>.py       the reader of one metric (`read(run)`)
    models/<model>.py         the architecture a configuration names

Nothing here lists a cell, a mix, a metric or a model: a new one is a
new file plus an entry in `BENCHMARK.json` (a model, the `model` key of
its configuration).
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = HERE.parents[1]                               # the checkout
BENCHMARK = ROOT / "BENCHMARK.json"
MODELS = HERE / "models"
DEFAULT_MODEL = "gpt_block"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    kind: str                   # "end_to_end" | "per_layer"
    workloads: Optional[List[str]]

    def applies_to(self, workload: str) -> bool:
        return self.workloads is None or workload in self.workloads


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: List[Metric]       # this cell's, end-to-end then per-layer


def benchmark(path: Path = BENCHMARK) -> dict:
    return load_json(path)


def config(name: str) -> dict:
    return load_json(HERE / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def metrics(bench: dict) -> List[Metric]:
    out = []
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            out.append(Metric(m["name"], m["unit"], kind, m.get("workloads")))
    return out


def cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else benchmark()
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")
    return Cell(name, int(entry["chips"]), config(entry["config"]),
                traffic(entry["traffic"]),
                [m for m in metrics(bench) if m.applies_to(name)])


def _load(name: str, path: Path) -> ModuleType:
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable:
    """The `read(run)` function of metrics/<metric>.py."""
    path = HERE / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {metric!r} has no reader at {path}")
    return _load(f"chipbench_metric_{metric.replace('.', '_')}", path).read


_MODELS: Dict[Path, ModuleType] = {}


def model(cfg: dict) -> ModuleType:
    """The module of models/<name>.py for the configuration's `model`
    key (`gpt_block` where it has none), loaded once per process; an
    unknown name is an error."""
    name = cfg.get("model", DEFAULT_MODEL)
    path = MODELS / f"{name}.py"
    if path not in _MODELS:
        if not path.is_file():
            raise KeyError(f"no model {name!r}: {path} does not exist")
        _MODELS[path] = _load(f"chipbench_model_{name.replace('.', '_')}",
                              path)
    return _MODELS[path]


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; an unknown kind is an error."""
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]
