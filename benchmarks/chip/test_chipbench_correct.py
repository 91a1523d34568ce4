"""CPU checks of the comparison that decides `correct`, at a tiny width
with the configuration's own limits: a sound run passes; the
lower-precision control fails; and a run whose timed path is broken
underneath (its step leaves the state unchanged, leaves half of the
batch out, or skips the exchange between DP replicas; or a recovery
hands a joiner its state without Adam's moments) fails. The runs skip
only the harness's look for a chip."""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import calibrate  # noqa: E402
from chipbench import compare, main, spec  # noqa: E402
from repro.core.engine import PipelineEngine  # noqa: E402

SEED = 2 ** 31 + 99          # larger than 32 signed bits hold
TINY = dict(num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
            head_dim=16, d_ff=64, vocab_size=128, seq_len=16)


def tiny_cell(workload: str) -> spec.Cell:
    """The workload's cell at a tiny width. A pair of configuration and
    traffic that BENCHMARK.json does not list, `<config>.<traffic>`, is
    built from their files, with no metrics."""
    if workload in {w["name"] for w in spec.benchmark()["workloads"]}:
        cell = spec.cell(workload)
    else:
        config, traffic = workload.rsplit(".", 1)
        cell = spec.Cell(workload, 1, spec.config(config),
                         spec.traffic(traffic), [])
    return spec.Cell(cell.name, cell.chips, {**cell.config, **TINY},
                     cell.traffic, cell.metrics)


@pytest.fixture(scope="module", autouse=True)
def shared_programs():
    """Jobs of one tiny configuration compile identical programs; share
    them between the engines of this module to keep it to seconds."""
    memo = {}
    compile_role, bucket_reduce = (PipelineEngine.compile_role,
                                   PipelineEngine.bucket_reduce_fn)

    def key(eng, *rest):
        return (repr(eng.cfg), eng.pp, eng.dp, eng.mb_size, eng.seq_len,
                str(eng.param_dtype), eng.adam) + rest

    def role(self, stage, fresh=False, charge=None):
        k = key(self, "role", stage)
        if k not in memo:
            memo[k] = compile_role(self, stage, fresh=fresh)
        r = memo[k]
        if not fresh:
            self._role_cache[stage] = r
        if charge is not None:
            self.clock.advance(self.compile_charge(r), f"jit:{stage}",
                               lane=charge)
        return r

    def reduce(self, stage):
        k = key(self, "reduce", stage)
        if k not in memo:
            memo[k] = bucket_reduce(self, stage)
        return memo[k]

    PipelineEngine.compile_role = role
    PipelineEngine.bucket_reduce_fn = reduce
    yield
    PipelineEngine.compile_role = compile_role
    PipelineEngine.bucket_reduce_fn = bucket_reduce


def _run(workload: str) -> dict:
    return main.run_cell(tiny_cell(workload), SEED, 0.0, False,
                         time.perf_counter())


# ------------------------------------------------ faults in the program
# Each wraps the engine's reduce-and-update, which still runs (and
# records its collectives for the standbys' replay) before the fault
# replaces what it wrote.
STATE = ("param_segs", "params", "_seg_stage", "opt", "step")


def _unchanged(orig):
    """The step returns every machine's state unchanged."""
    def step(self, grads_acc, navg, it, t_comp, lane):
        before = {k: {f: self.machine(*k).payload.get(f) for f in STATE}
                  for k in grads_acc}
        orig(self, grads_acc, navg, it, t_comp, lane)
        for k, state in before.items():
            self.machine(*k).payload.update(state, step=it + 1)
    return step


def _half_batch(orig):
    """The second half of the DP replicas' gradients is left out, and
    the mean is taken over the rest."""
    def step(self, grads_acc, navg, it, t_comp, lane):
        kept = {k: (g if k[0] < self.dp // 2
                    else jax.tree.map(jnp.zeros_like, g))
                for k, g in grads_acc.items()}
        orig(self, kept, navg / 2, it, t_comp, lane)
    return step


def _no_exchange(orig):
    """Each DP replica steps on its own gradient alone."""
    def step(self, grads_acc, navg, it, t_comp, lane):
        opts = {k: self.machine(*k).payload["opt"] for k in grads_acc}
        orig(self, grads_acc, navg, it, t_comp, lane)
        for (d, s), opt in opts.items():
            own = [grads_acc[(e, s)] if e == d else
                   jax.tree.map(jnp.zeros_like, grads_acc[(e, s)])
                   for e in range(self.dp)]
            segs, new_opt, _ = self.compile_role(s).fns["update"](
                self.bucket_reduce_fn(s)(*own), opt, navg / self.dp)
            self.machine(d, s).payload.update(
                param_segs=segs, params=None, _seg_stage=s, opt=new_opt)
    return step


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "no_exchange": _no_exchange}


@pytest.mark.parametrize("workload", ["gpt-medium.steady",
                                      "gpt-medium.churn", "gpt-2.7b.churn"])
def test_sound_run_is_correct(workload):
    out = _run(workload)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault, workload", [
    pytest.param(f, w, id=f if w.endswith("steady") else f"{f}-churn")
    for w in ("gpt-medium.steady", "gpt-medium.churn")
    for f in sorted(FAULTS)])
def test_broken_step_is_not_correct(fault, workload, monkeypatch):
    step = FAULTS[fault](PipelineEngine._flat_reduce_and_update)
    monkeypatch.setattr(PipelineEngine, "_flat_reduce_and_update", step)
    out = _run(workload)
    assert not out["correct"], out["checks"]


def _half_copied(monkeypatch):
    """A migration's copy stops half-way through each parameter bucket:
    the joiner holds zeros in the rest."""
    unpack = PipelineEngine.set_state_flat

    def set_state_flat(self, mid, *args):
        unpack(self, mid, *args)
        p = self.cluster[mid].payload
        p["param_segs"] = tuple(g.at[g.size // 2:].set(0)
                                for g in p["param_segs"])

    monkeypatch.setattr(PipelineEngine, "set_state_flat", set_state_flat)


def _moments_lost(monkeypatch):
    """A restored machine gets its state without Adam's moments."""
    restore = PipelineEngine.set_state

    def set_state(self, mid, state):
        restore(self, mid, state)
        opt = self.cluster[mid].payload["opt"]
        self.cluster[mid].payload["opt"] = {
            **opt, "m": jax.tree.map(jnp.zeros_like, opt["m"]),
            "v": jax.tree.map(jnp.zeros_like, opt["v"])}

    monkeypatch.setattr(PipelineEngine, "set_state", set_state)


# The churn warm-up's migrations hand state over with set_state_flat
# (onto DP replica 1, whose Adam state the shared update never reads)
# and its failures restore the in-memory checkpoint with set_state
# (onto replica 0).
HAND_OFF = {"migration_half_copied": _half_copied,
            "failure_moments_lost": _moments_lost}


@pytest.mark.parametrize("fault, workload", [
    pytest.param(f, w, id=f if w == "gpt-medium.churn" else f"{f}-2.7b")
    for w in ("gpt-medium.churn", "gpt-2.7b.churn")
    for f in sorted(HAND_OFF)])
def test_broken_hand_off_is_not_correct(fault, workload, monkeypatch):
    HAND_OFF[fault](monkeypatch)
    out = _run(workload)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("config, steps", [
    pytest.param("gpt-medium", 3, id="gpt-medium"),
    pytest.param("gpt-2.7b", 3, id="gpt-2.7b"),
    pytest.param("gpt-2.7b", 5, id="gpt-2.7b-5steps")])
def test_lower_precision_control_is_not_correct(config, steps):
    cfg = {**spec.config(config), **TINY}
    numbers = calibrate.stand_ins(cfg, SEED, steps)["control"]
    assert not compare.judge(numbers, cfg["limits"]), numbers
