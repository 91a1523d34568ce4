"""Churn-storm fuzz harness.

Fast part (hypothesis property tests):
- `compute_dp_resize_plan` shrink -> grow round-trip over randomly
  ordered rings, splice points and revert paths: membership AND the
  exact connection set are restored, both via a matching grow plan and
  via `revert_delta` (dp_resize plans are self-inverse through
  `old_members`);
- `generate_churn_trace` well-formedness over sampled knob dicts:
  notices inside the CostModel window, straggle ramps ascending,
  every storm tailed by enough replenish events to re-grow;
- `dp_retire` / `dp_restaff` grid accounting: retiring a chain moves
  its logical ranks to the hosted overlay and frees the survivors,
  re-staffing restores the exact (d, s) key set.

Slow part: seeded random churn traces — wave intensity x notice
probability x pool size x bounded/elastic — driven end-to-end on the
real-exec engine. After every storm: bitwise loss parity with the
uninterrupted reference, per-channel SimClock ledger conservation,
grid/ring consistency, and the dp_resize round-trip (every retired
chain re-grown, hosted overlay empty, full (d, s) key set back).
"""
import random

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.cluster.costmodel import DEFAULT as COST
from repro.cluster.node import NodeStatus
from repro.core import campaign
from repro.core.groups import (CommGroup, GroupState, apply_delta,
                               compute_dp_resize_plan, revert_delta)

# the engine here charges the SimClock the modeled compile constant
# (CampaignCfg.sim_compile_seconds), so the stage programs that each
# fresh controller compiles again are loaded from a cache instead
pytestmark = pytest.mark.usefixtures("persistent_compile_cache")

FUZZ_CFG = campaign.CampaignCfg(
    layers=2, d_model=32, heads=2, vocab=64, global_batch=4,
    seq_len=16, micro_batches=1, warmup_iters=1, total_iters=4)


# ------------------------------------------------ fast: resize plans
@given(st.permutations(list(range(10, 16))),
       st.integers(min_value=3, max_value=6),
       st.integers(min_value=0, max_value=5),
       st.booleans())
@settings(max_examples=40)
def test_dp_resize_round_trip(order, n, i, use_revert):
    """Shrink one member out of a ring, bring it back (grow plan or
    revert_delta): membership and the exact connection set return."""
    members = list(order)[:n]
    i = i % n
    g = CommGroup("dp.s0", "dp", list(members), channels=4)
    g.establish_all()
    conns0 = set(g.connections)
    victim = members[i]

    shrink = compute_dp_resize_plan(g, remove=[victim])
    assert shrink.kind == "dp_resize"
    assert shrink.old_members == members
    apply_delta(g, shrink)
    assert victim not in g.members and g.validate_rings()

    if use_revert:
        revert_delta(g, shrink)           # self-inverse via old_members
        g.state = GroupState.ACTIVE
        g.pending_plan = g.pending_members = None
    else:
        grow = compute_dp_resize_plan(g, insert=[victim], index=i)
        apply_delta(g, grow)
    assert g.members == members
    assert set(g.connections) == conns0
    assert g.validate_rings()


@given(st.permutations(list(range(5))),
       st.integers(min_value=1, max_value=3))
@settings(max_examples=25)
def test_dp_resize_shrink_to_singleton_and_back(order, k):
    """Shrinking below two members must drop every connection (a
    singleton carries no rings) and still grow back exactly."""
    members = list(order)[:k + 1]
    g = CommGroup("pp.d1", "pp", list(members), channels=2)
    g.establish_all()
    conns0 = set(g.connections)
    gone = members[1:]
    shrink = compute_dp_resize_plan(g, remove=gone)
    apply_delta(g, shrink)
    assert g.members == members[:1]
    assert not g.connections and g.validate_rings()
    grow = compute_dp_resize_plan(g, insert=gone, index=1)
    apply_delta(g, grow)
    assert g.members == members and set(g.connections) == conns0


@given(st.dictionaries(
    st.sampled_from(["wave_rate_per_min", "notice_p", "rack_p",
                     "straggler_p"]),
    st.sampled_from([0.0, 0.4, 1.0, 4.0]),
    max_size=4),
    st.integers(min_value=0, max_value=2 ** 16))
@settings(max_examples=20)
def test_trace_generator_well_formed(knobs, seed):
    knobs = {k: (v if k == "wave_rate_per_min" else min(v, 1.0))
             for k, v in knobs.items()}
    if knobs.get("wave_rate_per_min") == 0.0:
        knobs["wave_rate_per_min"] = 0.5
    dp, pp = 2, 2
    tr = campaign.generate_churn_trace(seed, dp=dp, pp=pp,
                                       max_events=10, **knobs)
    assert tr.seed == seed
    # deterministic: the same seed and knobs reproduce the trace
    again = campaign.generate_churn_trace(seed, dp=dp, pp=pp,
                                          max_events=10, **knobs)
    assert tr == again
    # every storm ends with enough hand-backs to re-grow a retired
    # chain and refill the pool
    tail = [e.kind for e in tr.events[-(pp + 2):]]
    assert tail == ["replenish"] * (pp + 2), tail
    ramps = {}
    for e in tr.events:
        assert e.kind in ("preempt", "drain", "straggle", "replenish")
        if e.kind == "replenish":
            assert e.target == ""
            continue
        d, s = e.target[1:].split("s")
        assert 0 <= int(d) < dp and 0 <= int(s) < pp, e
        if e.kind in ("preempt", "drain"):
            assert e.notice_s == 0.0 or \
                COST.notice_min_s <= e.notice_s <= COST.notice_max_s
        if e.kind == "straggle":
            # gradual degradation: factors ramp upward per target
            assert e.factor > ramps.get(e.target, 1.0) or \
                e.factor == 1.05          # a fresh ramp restarts low
            ramps[e.target] = e.factor


def test_dp_retire_restaff_restores_grid():
    """Grid accounting of the degraded-mode shrink/re-grow pair, no
    training involved: retire chain d=1, hosted overlay covers its
    ranks, survivors freed to IDLE; re-staff restores the key set."""
    ctl = campaign.build_controller(FUZZ_CFG, standby_count=0)
    eng = ctl.engine
    keys0 = set(eng.grid)
    victim = eng.grid[(1, 0)]
    survivor = eng.grid[(1, 1)]
    ctl.cluster[victim].fail()
    freed = eng.dp_retire(1)
    assert set(eng.hosted) == {(1, 0), (1, 1)}
    assert set(eng.grid) == keys0 - {(1, 0), (1, 1)}
    assert freed == [survivor]
    assert ctl.cluster[survivor].status == NodeStatus.IDLE
    hosts = set(eng.hosted.values())
    assert hosts <= set(eng.grid.values())
    fresh = ctl.cluster.add_machine().mid
    eng.dp_restaff(1, {0: survivor, 1: fresh})
    assert not eng.hosted
    assert set(eng.grid) == keys0
    assert eng.grid[(1, 0)] == survivor and eng.grid[(1, 1)] == fresh
    assert ctl.cluster[survivor].status == NodeStatus.TRAINING


# --------------------------------------------- slow: seeded storm draws
def _assert_ledger_conserved(clock):
    assert clock.pending_async() == 0
    for ch, issued in clock.issued_by_channel.items():
        exposed = clock.exposed_by_channel.get(ch, 0.0)
        hidden = clock.hidden_by_channel.get(ch, 0.0)
        assert abs(issued - (exposed + hidden)) < 1e-9, \
            (ch, issued, exposed, hidden)


@pytest.fixture(scope="module")
def reference():
    return campaign.reference_run(FUZZ_CFG)


# (seed, wave_rate_per_min, notice_p, standby_count, bounded)
STORM_DRAWS = [
    (101, 1.0, 0.9, 1, False),   # gentle, mostly noticed, elastic pool
    (202, 4.0, 0.5, 2, True),    # intense mixed wave, bounded pool
    (303, 2.0, 0.0, 1, True),    # all hard failures, bounded pool
    (404, 6.0, 1.0, 1, False),   # dense all-noticed wave, elastic
]


@pytest.mark.slow
@pytest.mark.parametrize("seed,rate,notice_p,sb,bounded", STORM_DRAWS)
def test_random_churn_trace(seed, rate, notice_p, sb, bounded,
                            reference):
    ctl = campaign.build_controller(FUZZ_CFG, standby_count=sb)
    if bounded:
        ctl.elastic_pool = False
        ctl.degraded_mode = True
    eng = ctl.engine
    losses = {0: eng.losses[0]}
    campaign._train_to(ctl, 1 + FUZZ_CFG.warmup_iters, losses)
    # backstop for bounded draws whose storm exhausts the LAST chain
    # (no shrink possible -> checkpoint-restart fallback needs storage)
    ctl.save_to_storage()

    trace = campaign.generate_churn_trace(
        seed, dp=FUZZ_CFG.dp, pp=FUZZ_CFG.pp, wave_rate_per_min=rate,
        notice_p=notice_p, max_events=8)
    step0, nloss0 = eng.step_count, len(eng.losses)
    events = campaign.drive_churn_trace(ctl, trace)
    assert events >= 1, "draw injected nothing — pick another seed"
    # iterations committed inside the storm (straggler drains train one
    # overlapped iteration) land in the loss map; a rollback-and-retrain
    # appends duplicates, so the LAST k entries are the surviving steps
    k = eng.step_count - step0
    if k:
        tail = eng.losses[len(eng.losses) - k:]
        for i, st_ in enumerate(range(step0, eng.step_count)):
            losses[st_] = tail[i]

    # every retired chain re-grew off the trace's replenish tail
    assert not eng.hosted, (seed, eng.hosted)
    shrinks = sum(1 for r in ctl.reports if r.kind == "dp_shrink")
    regrows = sum(1 for r in ctl.reports if r.kind == "dp_regrow")
    assert shrinks == regrows, (seed, shrinks, regrows)
    if not bounded:
        assert shrinks == 0, "elastic pool must never degrade"

    # dp_resize round trip: the full physical grid is back, one machine
    # per slot, every ring whole, one committed epoch
    keys = {(d, s) for d in range(FUZZ_CFG.dp)
            for s in range(FUZZ_CFG.pp)}
    assert set(eng.grid) == keys
    mids = list(eng.grid.values())
    assert len(mids) == len(set(mids)), mids
    for m in mids:
        assert ctl.cluster[m].alive, m
    for g in eng.groups.values():
        assert g.state == GroupState.ACTIVE and g.pending_plan is None
        assert g.validate_rings(), g.gid
    assert len(set(eng.epoch_signature().values())) == 1

    # ledger conservation, then bitwise parity with the reference
    _assert_ledger_conserved(ctl.clock)
    campaign._train_to(ctl, 1 + FUZZ_CFG.total_iters, losses)
    _assert_ledger_conserved(ctl.clock)
    assert set(losses) == set(reference)
    assert all(losses[s] == reference[s] for s in reference), \
        (seed, rate, notice_p, sb, bounded)
