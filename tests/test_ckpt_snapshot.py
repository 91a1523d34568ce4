"""The in-memory checkpoint on the flat path: a snapshot holds the flat
optimizer state, the step and the stage, and no params; a restore
rebuilds the params from the master weights, bitwise the params the
update made. Run in float32 and with bf16 block weights (two-dtype
buckets)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.node import Cluster
from repro.cluster.simclock import SimClock
from repro.configs.gpt import tiny_gpt
from repro.core import tracing
from repro.core.controller import Controller
from repro.core.engine import PipelineEngine
from repro.core.sandbox import CommHooks
from repro.train.checkpoint import tree_bytes

CFG = tiny_gpt(layers=4, d=64, heads=4, vocab=256)

# real XLA compiles of two tiny jobs a dtype
pytestmark = pytest.mark.slow


def build(param_dtype) -> Controller:
    cluster = Cluster(9, device_capacity=16 * 2 ** 30)
    clock = SimClock()
    eng = PipelineEngine(CFG, dp=2, pp=2, global_batch=8, seq_len=32,
                         cluster=cluster, clock=clock, comm=CommHooks(clock),
                         micro_batches=2, param_dtype=param_dtype)
    ctl = Controller(eng, standby_count=1)
    ctl.bootstrap_job(list(range(4)))
    return ctl


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def runs(request):
    """An uninterrupted job, and one whose (0, 1) machine fails after 2
    iterations and is restored from the in-memory checkpoint."""
    dtype = jnp.dtype(request.param)
    ref = build(dtype)
    ref_losses = ref.train(5)
    ctl = build(dtype)
    losses = ctl.train(2)
    snaps = {ds: ctl.imc.get(mid)[1] for ds, mid in ctl.engine.grid.items()}
    tracing.reset()
    tracing.enable()
    try:
        rep = ctl.unexpected_failure(ctl.engine.grid[(0, 1)])
    finally:
        tracing.disable()
    counts = tracing.counts()
    tracing.reset()
    eng = ctl.engine
    joiner, peer = eng.grid[(0, 1)], eng.grid[(1, 1)]
    restored = eng.flat_spec(1).flatten(eng.cluster[joiner].payload["params"])
    live = eng.cluster[peer].payload["param_segs"]
    losses += ctl.train(3)
    return dict(ctl=ctl, snaps=snaps, rep=rep, counts=counts,
                restored=restored, live=live, losses=losses,
                ref_losses=ref_losses)


def _snapshot_holds_no_params(r):
    eng = r["ctl"].engine
    for (_, s), snap in r["snaps"].items():
        spec = eng.flat_spec(s)
        assert set(snap) == {"opt", "step", "stage"}
        assert snap["stage"] == s
        # m, v and the master weights in fp32, and Adam's int32 step
        assert tree_bytes(snap["opt"]) == 3 * 4 * spec.size + 4
        # the step and the stage, int64 on the host
        assert tree_bytes(snap) == tree_bytes(snap["opt"]) + 8 + 8


def _restore_rebuilds_params_bitwise(r):
    assert r["rep"].state_path == "neighbor"
    assert [c.n for c in r["counts"]
            if c.name == "ckpt.params_rebuilt"] == [1]
    assert len(r["restored"]) == len(r["live"])
    for a, b in zip(r["restored"], r["live"]):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(r["losses"], r["ref_losses"], rtol=0, atol=0)


CHECKS = {"snapshot": _snapshot_holds_no_params,
          "restore": _restore_rebuilds_params_bitwise}


@pytest.mark.parametrize("check", sorted(CHECKS))
def test_flat_checkpoint(runs, check):
    CHECKS[check](runs)
