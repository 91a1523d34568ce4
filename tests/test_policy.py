"""PolicyEngine (core/policy.py): the telemetry-driven `auto` policy
that retired the fixed reshard_min_fraction >= 0.5 threshold.

Three layers of evidence, cheapest first:

- engine unit pins: feasibility tiers and the ranking order at
  hand-built telemetry (re-shard beats migrate down to the safety
  clamp, total loss migrates, dp_shrink only on a dry pool, nothing
  feasible raises);
- crossover pins against the checked-in ``BENCH_scale.json``
  ``policy_boundary`` sweep — the MEASURED decision boundary the
  engine's predictions must agree with, row by row, with regret
  exactly 0.0;
- a seeded fuzz draw (hypothesis ``fixed_dictionaries`` over the
  fault knobs) asserting ``policy_regret_s == 0.0`` and bitwise loss
  parity for every drawn fault, and a crash-adoption test proving the
  journaled decision record replays identically through
  ``Controller.restart()`` instead of being re-decided.
"""
import json
import os
import sys

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.cluster.costmodel import DEFAULT as COST
from repro.core import campaign
from repro.core.campaign import (CampaignCfg, Scenario, build_controller,
                                 run_policy_axis)
from repro.core.migration import ControllerCrash, CrashPoint, MigState
from repro.core.policy import (KNOWN_POLICIES, PolicyEngine, Telemetry)

# the engine here charges the SimClock the modeled compile constant
# (CampaignCfg.sim_compile_seconds), so the stage programs that each
# fresh controller compiles again are loaded from a cache instead
pytestmark = pytest.mark.usefixtures("persistent_compile_cache")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

ENGINE = PolicyEngine(COST)


def _tele(**over) -> Telemetry:
    """Telemetry at a representative mid-size fault: one victim with
    some surviving devices, a healthy pool, storage reachable."""
    base = dict(victim=0, surviving_fraction=0.5,
                state_bytes=2 * 10 ** 9, standbys=1, idle_spares=2,
                elastic_pool=False, degraded_mode=False,
                can_shrink=True, dp=2, pp=2, affected_groups=3,
                channels=COST.channels_per_group, storage_ok=True,
                storage_bw=COST.bw_storage_per_gpu, notice_s=0.0,
                model_params=1e9, total_gpus=32)
    base.update(over)
    return Telemetry(**base)


# ------------------------------------------------- engine unit pins
def test_reshard_beats_migrate_down_to_the_safety_clamp():
    """The bug this PR fixes: the old fixed threshold migrated below
    f=0.5 even though a measured re-shard is cheaper all the way down
    to the clamp. The engine must rank re-shard first at every
    surviving fraction the clamp allows."""
    for lose in range(1, 8):
        f = (8 - lose) / 8
        d = ENGINE.decide(_tele(surviving_fraction=f), "gpu_fault")
        assert d.chosen == "reshard", (f, d.chosen)
        assert d.cost_of("reshard").downtime_s \
            < d.cost_of("migrate").downtime_s, f


def test_total_loss_migrates():
    d = ENGINE.decide(_tele(surviving_fraction=0.0), "gpu_fault")
    assert d.chosen == "migrate"
    assert not d.cost_of("reshard").feasible


def test_clamp_is_a_feasibility_gate_not_a_preference():
    below = COST.reshard_min_fraction / 2
    d = ENGINE.decide(_tele(surviving_fraction=below), "gpu_fault")
    assert not d.cost_of("reshard").feasible
    assert d.chosen == "migrate"


def test_dp_shrink_needs_a_dry_pool_and_degraded_mode():
    wet = ENGINE.decide(_tele(surviving_fraction=0.0), "gpu_fault")
    assert not wet.cost_of("dp_shrink").feasible
    dry = ENGINE.decide(
        _tele(surviving_fraction=0.0, standbys=0, idle_spares=0,
              degraded_mode=True), "gpu_fault")
    assert dry.chosen == "dp_shrink"


def test_ckpt_restart_is_the_storage_gated_last_resort():
    d = ENGINE.decide(
        _tele(surviving_fraction=0.0, standbys=0, idle_spares=0),
        "failure")
    assert d.chosen == "ckpt_restart"
    with pytest.raises(ValueError):
        ENGINE.decide(
            _tele(surviving_fraction=0.0, standbys=0, idle_spares=0,
                  storage_ok=False), "failure")


def test_notice_window_hides_the_state_ship():
    """A long preemption notice overlaps the ship with training: the
    hidden portion must move downtime -> overlap, never vanish."""
    short = ENGINE.decide(_tele(notice_s=0.0), "preemption")
    long = ENGINE.decide(_tele(notice_s=3600.0), "preemption")
    s, l = short.cost_of("migrate"), long.cost_of("migrate")
    assert l.downtime_s < s.downtime_s
    assert l.overlap_s > s.overlap_s


def test_decision_record_is_json_plain_and_complete():
    d = ENGINE.decide(_tele(), "gpu_fault")
    rec = json.loads(json.dumps(d.to_record()))
    assert rec["chosen"] == d.chosen
    assert [c["policy"] for c in rec["ranking"]] \
        == [c.policy for c in d.costs]
    assert set(rec["telemetry"]) == set(_tele().to_record())
    assert all(p in KNOWN_POLICIES for p in
               (c["policy"] for c in rec["ranking"]))


# ---------------------------- crossover pins vs the measured boundary
@pytest.fixture(scope="module")
def boundary():
    with open(os.path.join(_REPO, "BENCH_scale.json")) as f:
        payload = json.load(f)
    assert "policy_boundary" in payload, \
        "BENCH_scale.json predates the policy sweep - regenerate it"
    return payload


def test_measured_boundary_has_zero_regret(boundary):
    bd = boundary["policy_boundary"]
    assert bd["regret_max_s"] == 0.0
    for row in bd["rows"]:
        assert row["regret_s"] == 0.0, row
        assert row["auto_choice"] == row["best_fixed"], row


def test_measured_boundary_sits_at_the_safety_clamp(boundary):
    bd = boundary["policy_boundary"]
    assert bd["safety_clamp"] == COST.reshard_min_fraction == 0.125
    assert bd["reshard_wins_down_to_fraction"] == bd["safety_clamp"]
    claims = boundary["claims"]
    assert claims["policy_regret_max_s"] == 0.0
    assert claims["policy_reshard_wins_down_to_fraction"] == 0.125


def test_predictions_agree_with_measurements_row_by_row(boundary):
    """Per measured row: the engine's predicted breakdown (recorded by
    the sweep next to the measurement) ranks the policies in the same
    order the stopwatch did, and the winner matches."""
    for row in boundary["policy_boundary"]["rows"]:
        pred = row["predicted"]
        feas = {p: c for p, c in pred.items() if c["feasible"]}
        pred_best = min(feas, key=lambda p: feas[p]["downtime_s"])
        assert pred_best == row["auto_choice"], row
        measured = {"reshard": row["reshard_s"],
                    "migrate": row["migrate_s"]}
        for a in measured:
            for b in measured:
                if measured[a] is None or measured[b] is None:
                    continue
                if a in feas and b in feas \
                        and measured[a] < measured[b]:
                    assert pred[a]["downtime_s"] \
                        <= pred[b]["downtime_s"], (a, b, row)


# --------------------------------------- seeded regret fuzz (slow)
FUZZ_CFG = CampaignCfg(
    layers=2, d_model=32, heads=2, vocab=64, global_batch=4,
    seq_len=16, micro_batches=1, warmup_iters=1, total_iters=4)

_KNOBS = st.fixed_dictionaries({
    "lose_gpus": st.integers(min_value=1, max_value=8),
    "standby_count": st.integers(min_value=0, max_value=2),
})


@pytest.fixture(scope="module")
def fuzz_reference():
    return campaign.reference_run(FUZZ_CFG)


@pytest.mark.slow
@given(_KNOBS)
@settings(max_examples=5, deadline=None)
def test_fuzzed_fault_knobs_never_regress_regret_or_parity(
        fuzz_reference, knobs):
    """Any drawn (lost-GPU count x pool size) combination: `auto` must
    match the best feasible fixed policy bit-for-bit (regret exactly
    0.0, not approximately) and preserve loss parity on every
    counterfactual run. A failing knob dict shrinks through hypothesis'
    fixed_dictionaries strategy to the minimal failing config."""
    sc = Scenario("fuzz-gpu", "gpu_degrade", "d0s0", "between_iter",
                  "reshard", {"policy": "auto", **knobs})
    rows = run_policy_axis([sc], FUZZ_CFG, fuzz_reference)
    assert len(rows) == 1
    row = rows[0]
    assert row["policy_regret_s"] == 0.0, row
    assert row["auto_never_worse"], row
    assert row["loss_parity"], row
    assert row["auto_choice"] in row["feasible"]


# -------------------------------------- crash adoption of a decision
@pytest.mark.slow
def test_journaled_decision_replays_identically_after_restart():
    """The decision is durable BEFORE dispatch: a controller crash
    inside the chosen recovery leaves the decision record in the
    journal, and the restarted controller adopts the run it picked —
    it does NOT re-decide. The adopted record is bit-identical to the
    one an uninterrupted controller journals for the same fault."""
    cfg = CampaignCfg(warmup_iters=1, total_iters=4)
    reference = campaign.reference_run(cfg)

    def fault(ctl, crash=None):
        victim = ctl.engine.grid[(0, 0)]
        return victim, ctl.gpu_fault(victim, policy="auto", lose=2,
                                     crash=crash)

    # uninterrupted twin: same fault, no crash
    ctl_ref = build_controller(cfg, standby_count=1)
    campaign._train_to(ctl_ref, 1 + cfg.warmup_iters, {})
    _, rep_ref = fault(ctl_ref)
    ref_policies = ctl_ref.journal.replay()["policies"]
    assert len(ref_policies) == 1
    assert ref_policies[0]["chosen"] == "reshard"

    ctl = build_controller(cfg, standby_count=1)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + cfg.warmup_iters, losses)
    with pytest.raises(ControllerCrash):
        fault(ctl, crash=CrashPoint("switch", 0))

    ctl2 = ctl.restart()
    state = ctl2.journal.replay()
    # exactly one decision: adoption replayed it, never re-consulted
    assert len(state["policies"]) == 1
    rec = state["policies"][0]
    assert rec == ref_policies[0]
    assert rec["chosen"] == "reshard"
    assert [c["policy"] for c in rec["ranking"]] \
        == [c["policy"] for c in ref_policies[0]["ranking"]]
    # the adopted run drove the chosen recovery to COMMITTED
    assert ctl2.last_run.state == MigState.COMMITTED
    assert ctl2.reports and ctl2.reports[-1].kind == "gpu_reshard"
    # the victim stayed in the grid (re-shard, not migrate) and the
    # interrupted timeline still converges bit-for-bit
    victim = ctl_ref.engine.grid[(0, 0)]
    assert victim in ctl2.engine.grid.values()
    campaign._train_to(ctl2, 1 + cfg.total_iters, losses)
    assert set(losses) == set(reference)
    assert max(abs(losses[k] - reference[k]) for k in reference) == 0.0

