"""The system under test, built and driven as the runtime's users do.

A job is a `PipelineEngine` under a `Controller`, bootstrapped with
`bootstrap_job` (which trains step 0 under the record hook). A traffic
mix is a list of actions, "train" (one `Controller.train(1)`) and
"event" (the next interruption of the mix's rotation), run once as
warm-up and then repeated through the measured window.

The program's readings for the correctness check are taken here too:
the first gradient as Adam received it (from Adam's first moment after
step 0) and Adam's master weights after the warm-up's updates.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec
from repro.cluster.node import Cluster
from repro.cluster.simclock import SimClock
from repro.core.controller import Controller
from repro.core.engine import PipelineEngine
from repro.core.sandbox import CommHooks
from repro.train.optimizer import AdamCfg


def build(cfg: dict, seed: int) -> Controller:
    """A bootstrapped job: weights and data from `seed` on the device."""
    dp, pp, standby = cfg["dp"], cfg["pp"], cfg["standby"]
    cluster = Cluster(dp * pp + 2 + standby, device_capacity=32 * 2 ** 30)
    clock = SimClock()
    engine = PipelineEngine(
        spec.model(cfg).arch(cfg), dp=dp, pp=pp,
        global_batch=cfg["global_batch"], seq_len=cfg["seq_len"],
        cluster=cluster, clock=clock, comm=CommHooks(clock),
        micro_batches=cfg["micro_batches"], seed=seed,
        adam=AdamCfg(**cfg["optimizer"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))
    ctl = Controller(engine, standby_count=standby)
    ctl.bootstrap_job(list(range(dp * pp)))
    return ctl


def block(ctl: Controller) -> None:
    """Wait until every training machine's state is on the device."""
    jax.block_until_ready([
        (ctl.cluster[mid].payload.get("param_segs"),
         ctl.cluster[mid].payload.get("opt"))
        for mid in ctl.engine.grid.values()])


# ------------------------------------------------------------- spans
@dataclass
class Spans:
    """Host spans of the benchmark's own calls into the runtime, kept in
    memory. With `annotate` each one is also a profiler TraceAnnotation,
    so the device trace can label its idle gaps by them."""
    annotate: bool = False
    items: List[Tuple[str, float, float]] = field(default_factory=list)

    def run(self, name: str, fn: Callable, *args, **kw):
        t0 = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation(name):
                out = fn(*args, **kw)
        else:
            out = fn(*args, **kw)
        self.items.append((name, t0, time.perf_counter()))
        return out

    def total(self, name: str, since: float = -np.inf) -> float:
        return sum(b - a for n, a, b in self.items if n == name and a >= since)

    def count(self, name: str, since: float = -np.inf) -> int:
        return sum(1 for n, a, _ in self.items if n == name and a >= since)


def instrument_checkpoint(ctl: Controller, spans: Spans) -> None:
    """Time the per-iteration in-memory checkpoint (engine.get_state +
    imc.put for every training machine) on this instance. The update's
    device work is waited for first, so the span holds the copies."""
    tick = ctl._tick_checkpoints

    def timed():
        block(ctl)
        spans.run("ckpt_put", tick)

    ctl._tick_checkpoints = timed


# ------------------------------------------------------------ driving
EVENT_SPAN = {"expected_migration": "migration",
              "unexpected_failure": "failure"}


class MixRunner:
    """Runs a traffic mix's actions on one job. An event kind names a
    `Controller` recovery method, called with the victim's machine id
    (`expected_migration` with a list of them)."""

    def __init__(self, ctl: Controller, traffic: dict, spans: Spans):
        self.ctl, self.spans = ctl, spans
        self.kinds = traffic["events"]["kinds"]
        self.victims = [tuple(v) for v in traffic["events"]["victims"]]
        self.n_events = 0
        self.losses: List[float] = []

    def act(self, action: str) -> None:
        if action == "train":
            self.losses += self.spans.run("train", self.ctl.train, 1)
        elif action == "event":
            self.event()
        else:
            raise ValueError(f"unknown traffic action {action!r}")

    def event(self) -> None:
        i = self.n_events
        kind = self.kinds[i % len(self.kinds)]
        victim = self.ctl.engine.grid[self.victims[i % len(self.victims)]]
        call = getattr(self.ctl, kind)
        arg = [victim] if kind == "expected_migration" else victim

        def recover():
            call(arg)
            block(self.ctl)

        self.spans.run(EVENT_SPAN.get(kind, kind), recover)
        self.n_events += 1


# ------------------------------------------------- program's readings
def _leaf_name(path) -> str:
    keys = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
    return ".".join(k for k in keys if k not in ("stack", "scan"))


def _named(tree) -> Dict[str, Any]:
    return {_leaf_name(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _distinct_replicas(engine: PipelineEngine, s: int) -> List[int]:
    """DP replicas of stage s whose optimizer state is a distinct array
    (after an update every replica shares the broadcast result)."""
    out, seen = [], []
    for d in range(engine.dp):
        opt = engine.machine(d, s).payload["opt"]
        if not any(o is opt for o in seen):
            seen.append(opt)
            out.append(d)
    return out


def first_grads(ctl: Controller, b1: float
                ) -> Dict[Tuple[int, int], Dict[str, np.ndarray]]:
    """{(d, s): {leaf: host copy}} of the gradient Adam took in step 0,
    read back from its first moment: m = (1 - b1) * g after one step."""
    return {k: {n: x / (1.0 - b1) for n, x in leaves.items()}
            for k, leaves in _opt_leaves(ctl, "m").items()}


def master_weights(ctl: Controller
                   ) -> Dict[Tuple[int, int], Dict[str, np.ndarray]]:
    """{(d, s): {leaf: host copy}} of Adam's master weights."""
    return _opt_leaves(ctl, "master")


def _opt_leaves(ctl: Controller, part: str):
    eng, out = ctl.engine, {}
    for s in range(eng.pp):
        for d in _distinct_replicas(eng, s):
            named = _named(eng.opt_state_tree(d, s)[part])
            out[(d, s)] = dict(zip(named, jax.device_get(
                list(named.values()))))
    return out
