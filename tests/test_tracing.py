"""The runtime's tracer (`repro.core.tracing`): the tracer alone, and a
tiny job (the fuzz harness's model) through a migration and a failure
with tracing on."""
import gc

import jax
import pytest

from repro.core import campaign, tracing
from repro.train.checkpoint import tree_bytes
from test_fuzz_victims import FUZZ_CFG

HIT, MISS = "/jax/compilation_cache/cache_hits", \
    "/jax/compilation_cache/cache_misses"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


@pytest.fixture(autouse=True)
def fresh_tracer():
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


def test_spans_nest_and_record_their_parents():
    tracing.enable()
    with tracing.span("tm:a", k=1):
        with tracing.span("tm:b"):
            tracing.count("n", 3)
        with tracing.span("tm:c"):
            pass
    with tracing.span("tm:d"):
        pass
    tracing.disable()
    spans = [s for s in tracing.records() if s.name != "tm:gc"]
    assert [(s.name, s.parent) for s in spans] == \
        [("tm:a", -1), ("tm:b", 0), ("tm:c", 0), ("tm:d", -1)]
    a, b, c, d = spans
    assert a.attrs == {"k": 1} and b.attrs == {}
    assert a.start <= b.start <= b.end <= c.start <= c.end <= a.end \
        <= d.start <= d.end
    (cnt,) = tracing.counts()
    assert cnt.name == "n" and cnt.n == 3 and b.start <= cnt.t <= b.end


def test_off_records_nothing():
    off = tracing.span("tm:a", k=1)
    assert off is tracing.span("tm:b")
    with off:
        tracing.count("n", 1)
    gc.collect()
    jax.monitoring.record_event(HIT)
    assert tracing.records() == [] and tracing.counts() == []


def test_gc_pause_is_a_span():
    tracing.enable()
    with tracing.span("tm:outer"):
        gc.collect()
    spans = tracing.records()
    outer = [i for i, s in enumerate(spans) if s.name == "tm:outer"]
    pauses = [s for s in spans if s.parent in outer and s.name == "tm:gc"]
    assert any(s.attrs["generation"] == 2 and "collected" in s.attrs
               for s in pauses)


def test_cache_listeners_count_and_disable_removes_them():
    tracing.enable()
    jax.monitoring.record_event(HIT)
    jax.monitoring.record_event(MISS)
    jax.monitoring.record_event(HIT)
    jax.monitoring.record_event_duration_secs(RETRIEVAL, 0.25)
    tracing.disable()
    got = [(c.name, c.n) for c in tracing.counts()]
    assert got == [("compile_cache.hits", 1), ("compile_cache.misses", 1),
                   ("compile_cache.hits", 1),
                   ("compile_cache.retrieval_s", 0.25)]
    jax.monitoring.record_event(HIT)
    jax.monitoring.record_event_duration_secs(RETRIEVAL, 0.25)
    gc.collect()
    assert [(c.name, c.n) for c in tracing.counts()] == got
    assert not any(s.name == "tm:gc" for s in tracing.records())
    assert tracing._on_gc not in gc.callbacks


def test_job_recoveries_steps_and_checkpoint_bytes():
    # no standby: the failure takes an elastic joiner, whose promotion
    # compiles and runs a shadow iteration, as a migration's warm-up does
    ctl = campaign.build_controller(FUZZ_CFG, standby_count=0)
    tracing.enable()
    ctl.train(1)
    leaver = ctl.engine.grid[(0, 1)]
    mig = ctl.expected_migration([leaver])
    fail = ctl.unexpected_failure(ctl.engine.grid[(1, 0)])
    ctl.train(1)
    tracing.disable()
    spans = tracing.records()
    counts = tracing.counts()

    def kids(span):
        i = spans.index(span)
        return [s for s in spans if s.parent == i and s.name != "tm:gc"]

    def counted(name, span):
        return sum(c.n for c in counts
                   if c.name == name and span.start <= c.t <= span.end)

    # one top-level tm:recovery per recovery; its children are the
    # steps the run executed, in journal order
    recs = [s for s in spans if s.name == "tm:recovery"]
    assert [(s.attrs["kind"], s.parent) for s in recs] == \
        [("expected", -1), ("unexpected", -1)]
    steps = {}
    for rec, rep in zip(recs, (mig, fail)):
        got = kids(rec)
        assert [s.attrs["step"] for s in got] == rep.journal
        assert all(s.name == "tm:step:" + s.attrs["step"].split(":")[0]
                   for s in got)
        steps[rep.kind] = {s.attrs["step"]: s for s in got}

    # a joiner's warm-up compiles its role inside a shadow iteration
    for step in (steps["expected"][f"warmup:{leaver}"],
                 steps["unexpected"]["promote"]):
        (shadow,) = kids(step)
        assert shadow.name == "tm:shadow_iteration"
        assert [s.name for s in kids(shadow)] == ["tm:compile_role"]

    # the hand-off's bytes: out of the leaver, into the joiner, the same
    (out, into) = kids(steps["expected"]["xfer"])
    assert (out.name, into.name) == ("tm:get_state_flat", "tm:set_state_flat")
    assert counted("bytes.d2h", out) == counted("bytes.h2d", into) > 0
    (restore,) = kids(steps["unexpected"]["recover"])
    assert restore.name == "tm:set_state" and counted("bytes.h2d", restore)

    # each checkpoint: one tm:get_state and one tm:imc_put a machine, and
    # the bytes counted inside it are those of the states put
    ckpts = [s for s in spans if s.name == "tm:ckpt"]
    assert len(ckpts) == 2
    grid = list(ctl.engine.grid.values())
    assert [s.name for s in kids(ckpts[-1])] == \
        ["tm:get_state", "tm:imc_put"] * len(grid)
    assert counted("bytes.d2h", ckpts[-1]) == sum(
        tree_bytes(ctl.imc.get(mid)[1]) for mid in grid)

    # each iteration: a loss sync a micro-batch of each replica, then
    # one reduce-and-update
    iters = [s for s in spans if s.name == "tm:train_iteration"]
    assert len(iters) == 2
    assert [s.name for s in kids(iters[-1])] == ["tm:loss_sync"] * (
        FUZZ_CFG.dp * FUZZ_CFG.micro_batches) + ["tm:reduce_update"]

    # stable program names for the bucket drain and materialization
    fns = ctl.engine.compile_role(0).fns
    assert fns["flatten"].as_text().startswith("HloModule jit_flatten")
    assert fns["unflatten"].as_text().startswith("HloModule jit_unflatten")
