"""Interruption-scenario campaign: the paper's constant-downtime claim
as an executable matrix.

Fast part: matrix well-formedness, property-sampled over (dp, pp).
Slow part: the reduced scenario matrix end-to-end at dp=2/pp=2 — every
scenario must converge to bitwise loss parity with the uninterrupted
reference run, standby-recovery downtime must stay flat across
roles/timings while the full-reinit baseline exceeds it, and repeated
campaigns must serialize byte-identically (determinism)."""
import json

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import campaign

# the engine here charges the SimClock the modeled compile constant
# (CampaignCfg.sim_compile_seconds), so the stage programs that each
# fresh controller compiles again are loaded from a cache instead
pytestmark = pytest.mark.usefixtures("persistent_compile_cache")

KINDS = {"expected", "failure", "gpu_degrade", "straggler", "rebalance",
         "standby_loss", "controller_crash", "notice_drain",
         "churn_storm"}
TIMINGS = {"between_iter", "pre_reduce", "post_reduce",
           "during_migration", "during_prepare", "during_warmup",
           "mid_switchover", "mid_recovery",
           "concurrent_second_failure", "cascade"}
RECOVERIES = {"migration", "standby", "reshard", "ckpt_restart",
              "full_reinit", "replace", "replay", "degraded"}
VICTIM_TOKENS = {"joiner", "leaver", "standby"}


# ------------------------------------------------- fast: matrix shape
@given(st.sampled_from([2, 3]), st.sampled_from([2, 3, 4]))
@settings(max_examples=12)
def test_default_matrix_well_formed(dp, pp):
    m = campaign.default_matrix(dp, pp)
    names = [s.name for s in m]
    assert len(names) == len(set(names)), "scenario names must be unique"
    assert len(m) >= 33
    for s in m:
        assert s.kind in KINDS and s.timing in TIMINGS \
            and s.recovery in RECOVERIES, s
        roles = [s.role] + list(s.params.get("victims", []))
        if "migrate" in s.params:
            roles.append(s.params["migrate"])
        for role in roles:
            if role.startswith("d") and "s" in role:
                d, stage = role[1:].split("s")
                assert int(d) < dp and int(stage) < pp, (s.name, role)
        # victim sets: tokens are resolvable, entries unique, a token
        # only makes sense when an in-flight migration exists, and the
        # standby pool is provisioned for the victims that need one
        victims = list(s.params.get("victims", []))
        assert len(victims) == len(set(victims)), (s.name, victims)
        assert len(victims) <= 5, s.name
        for v in victims:
            if not (v.startswith("d") and "s" in v):
                assert v in VICTIM_TOKENS, (s.name, v)
                if v in ("joiner", "leaver"):
                    assert "migrate" in s.params, (s.name, v)
    # breadth: every kind, timing and recovery path is exercised, and
    # the victim-set axis reaches K in {2, 3, 5}
    assert {s.kind for s in m} == KINDS
    assert {s.timing for s in m} == TIMINGS
    assert {s.recovery for s in m} == RECOVERIES
    ks = {len(s.params["victims"]) for s in m if "victims" in s.params}
    assert {2, 3, 5} <= ks, ks


def test_reduced_matrix_is_subset():
    full = {s.name for s in campaign.default_matrix(2, 2)}
    reduced = campaign.reduced_matrix(2, 2)
    assert {s.name for s in reduced} <= full
    assert {s.recovery for s in reduced} >= {"standby", "full_reinit",
                                             "reshard"}
    # the push-CI slice exercises the mid-switch state machine and the
    # GPU-granular fault kind
    assert {s.timing for s in reduced} >= {"during_warmup",
                                           "mid_switchover"}
    assert "gpu_degrade" in {s.kind for s in reduced}


def test_reduced_covers_every_kind_and_timing():
    """Drift guard: REDUCED_NAMES is a hand-maintained tuple, so a
    rename in default_matrix (or a new axis value) could silently
    shrink the push-CI slice. Every reduced name must still exist in
    the full matrix — reduced_matrix drops unknown names without
    complaint — and the reduced slice must cover every kind and timing
    axis value the full matrix exercises."""
    full = {s.name: s for s in campaign.default_matrix(2, 2)}
    missing = [n for n in campaign.REDUCED_NAMES if n not in full]
    assert not missing, \
        f"REDUCED_NAMES drifted from default_matrix: {missing}"
    assert len(set(campaign.REDUCED_NAMES)) == len(campaign.REDUCED_NAMES)
    reduced = campaign.reduced_matrix(2, 2)
    assert len(reduced) == len(campaign.REDUCED_NAMES)
    for axis in ("kind", "timing"):
        full_vals = {getattr(s, axis) for s in full.values()}
        red_vals = {getattr(s, axis) for s in reduced}
        assert red_vals == full_vals, \
            f"reduced slice misses {axis} values: {full_vals - red_vals}"


@given(st.dictionaries(st.sampled_from(["dp", "pp"]),
                       st.sampled_from([2, 3]),
                       min_size=2, max_size=2))
@settings(max_examples=8)
def test_matrix_samples_as_dict(shape):
    """Scenario matrices are property-samplable as config dicts (drawn
    through hypothesis' dictionaries strategy)."""
    m = campaign.default_matrix(shape["dp"], shape["pp"])
    assert len(m) >= 20


# ------------------------------------- slow: reduced matrix end-to-end
CFG = campaign.CampaignCfg()


@pytest.fixture(scope="module")
def reference():
    return campaign.reference_run(CFG)


@pytest.fixture(scope="module")
def reduced_results(reference):
    return [campaign.run_scenario(sc, CFG, reference)
            for sc in campaign.reduced_matrix(CFG.dp, CFG.pp)]


@pytest.mark.slow
def test_every_scenario_bitwise_parity(reduced_results):
    for r in reduced_results:
        assert r.loss_parity, (r.name, r.loss_max_delta)
        assert r.steps == 1 + CFG.total_iters


@pytest.mark.slow
def test_standby_downtime_flat_full_reinit_not(reduced_results):
    """The constant-downtime figure shape: standby recovery is flat
    across roles and timings; the full-reinit baseline towers over it."""
    summary = campaign.summarize(reduced_results)
    standby = [r.downtime_per_event_s for r in reduced_results
               if r.recovery == "standby"]
    assert len(standby) >= 4           # roles x timings represented
    assert summary["standby_flat_within"] <= 1.5, summary
    assert summary["full_reinit_over_median"] > 1.5, summary
    assert summary["flat_claim_ok"], summary


@pytest.mark.slow
def test_standby_loss_is_zero_downtime(reduced_results):
    r = {x.name: x for x in reduced_results}["standby-loss"]
    assert r.downtime_s == 0.0
    assert r.overlap_s > 0.0           # replacement prep off-critical-path


@pytest.mark.slow
def test_mid_iteration_aborts_commit_nothing(reduced_results):
    """pre/post-reduce interrupts abort the iteration; recovery rolls
    back and the re-run reconverges bitwise (no lost iterations with
    per-iteration checkpoints)."""
    by = {x.name: x for x in reduced_results}
    for name in ("fail-first-pre_reduce", "fail-first-post_reduce"):
        assert by[name].lost_iterations == 0
        assert by[name].loss_parity
        assert by[name].recovery_path == "neighbor"


@pytest.mark.slow
def test_mid_switch_faults_resume_within_downtime_envelope(
        reduced_results):
    """Faults landing inside the switching machinery abort, roll back
    and resume — with per-event downtime inside the same 1.5x envelope
    as plain standby recovery, and bitwise parity preserved."""
    by = {x.name: x for x in reduced_results}
    summary = campaign.summarize(reduced_results)
    for name in ("fail-during-warmup", "fail-mid-switchover"):
        r = by[name]
        assert r.resumes == 1, name        # exactly one abort/resume
        assert r.loss_parity and r.lost_iterations == 0
    assert by["gpu-degrade-first"].resumes == 0   # no abort: planned leave
    assert by["gpu-degrade-first"].loss_parity
    assert summary["mid_switch_max_over_median"] <= 1.5, summary
    assert summary["mid_switch_claim_ok"], summary


@pytest.mark.slow
def test_victim_set_and_reshard_within_envelope(reduced_results):
    """The generalized-recovery slice of the reduced matrix: the K=3
    victim set (incl. the in-flight joiner) resumes off one abort with
    parity, the intra-machine re-shard keeps parity without migrating,
    and both stay inside the standby downtime envelope."""
    by = {x.name: x for x in reduced_results}
    k3 = by["fail-k3-joiner"]
    assert k3.events == 4 and k3.resumes == 1
    assert k3.loss_parity and k3.ckpt_fallbacks == 0
    rs = by["gpu-reshard-first"]
    assert rs.loss_parity and rs.resumes == 0
    assert rs.recovery_path == "dp_peer"
    assert rs.lost_iterations == 0
    summary = campaign.summarize(reduced_results)
    assert summary["mid_switch_claim_ok"], summary
    assert summary["n_victim_set_scenarios"] >= 2, summary
    # at tiny-GPT scale re-shard and migrate downtime are comparable;
    # the envelope (not superiority) is the claim under test
    assert 0.0 < summary["reshard_vs_migrate"] <= 1.5, summary


@pytest.mark.slow
def test_controller_crash_scenarios_recover_with_parity(reduced_results):
    """The control-plane slice: a crashed controller restarts from its
    journal, workers re-register, open runs are adopted and driven to
    commit — bitwise parity survives, no iterations are lost, and the
    restart+replay+adoption downtime stays inside the same 1.5x
    envelope as plain data-plane standby recovery."""
    by = {x.name: x for x in reduced_results}
    for name in ("crash-mid-switchover", "crash-mid-recovery",
                 "crash-with-victim"):
        r = by[name]
        assert r.loss_parity, (name, r.loss_max_delta)
        assert r.lost_iterations == 0, name
    # crash + in-flight migration + data-plane victim while down
    assert by["crash-with-victim"].events == 3
    assert by["crash-with-victim"].resumes >= 1
    summary = campaign.summarize(reduced_results)
    assert summary["controller_crash_claim_ok"], summary
    assert summary["controller_crash_max_over_median"] <= 1.5, summary
    assert summary["flat_claim_ok"], summary


@pytest.mark.slow
def test_reshard_mid_switch_fault_resumes(reduced_results):
    """A machine failure landing inside a re-shard run's own switch
    steps: the run aborts, rolls back, absorbs the victim via standby
    and resumes the re-shard against the new membership."""
    r = {x.name: x for x in reduced_results}["gpu-reshard-mid-switch"]
    assert r.events == 2
    assert r.resumes == 1
    assert r.loss_parity and r.lost_iterations == 0


@pytest.mark.slow
def test_campaign_is_deterministic():
    """One seed threads Controller + campaign: repeated runs emit a
    byte-identical BENCH payload (downtime ledger included)."""
    cfg = campaign.CampaignCfg(warmup_iters=1, total_iters=3)
    matrix = [s for s in campaign.default_matrix(cfg.dp, cfg.pp)
              if s.name in ("expected-first", "fail-first-standby")]
    a = campaign.run_campaign(matrix, cfg)
    b = campaign.run_campaign(matrix, cfg)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
