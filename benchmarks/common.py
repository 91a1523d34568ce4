"""Shared helpers for the paper-table benchmarks."""
from __future__ import annotations

import os
import sys
import time
from typing import Dict, List, Optional

# resolve from this file, not CWD, so benchmarks run from anywhere
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from repro.cluster.costmodel import DEFAULT as COST, CostModel
from repro.cluster.node import Cluster
from repro.cluster.simclock import SimClock
from repro.configs.gpt import FAMILY, tiny_gpt
from repro.core.controller import Controller
from repro.core.engine import PipelineEngine
from repro.core.sandbox import CommHooks
from repro.launch import compile_cache
from repro.models.registry import count_params

# A benchmark script run as a program (`python benchmarks/<name>.py` or
# `python -m benchmarks.run`) keeps its compiles in the persistent
# cache; a test that imports this module does not turn the cache on.
if os.path.dirname(os.path.abspath(sys.argv[0])) == os.path.join(
        _ROOT, "benchmarks"):
    compile_cache.enable()

# analytic parameter counts for the paper's models (cached)
_PARAMS: Dict[str, float] = {}

# nominal sizes for paper models with no FAMILY config; must stay
# disjoint from FAMILY so the counted and nominal sources can't drift
# apart for the same name (pinned by tests/test_bench_common.py)
_NOMINAL: Dict[str, float] = {"gpt-1t": 1e12}


def gpt_params(name: str) -> float:
    if name not in _PARAMS:
        if name in FAMILY:
            _PARAMS[name] = float(count_params(FAMILY[name]))
        else:
            assert not set(_NOMINAL) & set(FAMILY), \
                "nominal fallback may only carry names absent from " \
                "FAMILY (counted and nominal sources must not drift)"
            _PARAMS[name] = _NOMINAL[name]  # KeyError: unknown model
    return _PARAMS[name]


def build_realexec(dp=2, pp=2, layers=4, d=128, heads=4, vocab=512,
                   batch=8, seq=64, standby=1, machines=8,
                   cost: Optional[CostModel] = None,
                   use_flat_buffers: bool = True) -> Controller:
    """A CPU-runnable cluster: tiny GPT, real JAX compute + compiles."""
    cost = cost or COST
    cluster = Cluster(machines, device_capacity=16 * 2 ** 30)
    clock = SimClock()
    comm = CommHooks(clock, cost)
    eng = PipelineEngine(tiny_gpt(layers=layers, d=d, heads=heads,
                                  vocab=vocab), dp=dp, pp=pp,
                         global_batch=batch, seq_len=seq,
                         cluster=cluster, clock=clock, comm=comm,
                         cost=cost, micro_batches=2,
                         use_flat_buffers=use_flat_buffers)
    ctl = Controller(eng, cost=cost, standby_count=standby)
    return ctl


def emit(rows: List[dict], name: str) -> None:
    """Print a readable table block for a benchmark."""
    if not rows:
        return
    keys = list(rows[0].keys())
    print(f"\n== {name} ==")
    print(" | ".join(f"{k:>18s}" for k in keys))
    for r in rows:
        print(" | ".join(f"{_fmt(r.get(k)):>18s}" for k in keys))


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:,.3f}" if abs(v) < 1e5 else f"{v:,.0f}"
    return str(v)


def csv_line(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
