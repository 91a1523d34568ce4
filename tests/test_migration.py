"""Crash-consistent switching: the resumable migration state machine.

Fast part: MigrationRun mechanics (journal, fault points, done-step
skipping, partial-switch rollback) and groups.revert_delta, with no
engine.
Slow part: abort-at-every-step — kill an expected migration at each
journaled step kind on the real-exec engine and assert rollback
restores a consistent epoch, the async ledger drains, and the resumed
run reaches bitwise loss parity with an uninterrupted reference.
"""
import pytest

from repro.cluster.simclock import SimClock
from repro.core import campaign
from repro.core.groups import (CommGroup, GroupState, apply_delta,
                               compute_delta_plan, revert_delta)
from repro.core.migration import (FaultPoint, MidSwitchFault, MigState,
                                  MigrationRun, Step)

# the engine here charges the SimClock the modeled compile constant
# (CampaignCfg.sim_compile_seconds), so the stage programs that each
# fresh controller compiles again are loaded from a cache instead
pytestmark = pytest.mark.usefixtures("persistent_compile_cache")


# ------------------------------------------------ fast: run mechanics
def _run_with(steps, fault=None):
    run = MigrationRun(SimClock(), fault=fault)
    run.set_steps(steps)
    return run


def test_steps_execute_in_order_and_journal():
    seen = []
    steps = [Step("a", "prepare", lambda: seen.append("a"),
                  MigState.DELTA_PREPARED),
             Step("b", "barrier", lambda: seen.append("b"),
                  MigState.SWITCHING),
             Step("c", "commit", lambda: seen.append("c"),
                  MigState.COMMITTED)]
    run = _run_with(steps).execute()
    assert seen == ["a", "b", "c"]
    assert run.state == MigState.COMMITTED
    assert [e.step for e in run.journal] == ["a", "b", "c"]
    assert run.journal[-1].state == "committed"


def test_done_steps_skip_on_reexecute_but_state_still_applies():
    calls = []
    steps = [Step("a", "prepare", lambda: calls.append("a"),
                  MigState.DELTA_PREPARED),
             Step("b", "commit", lambda: calls.append("b"),
                  MigState.COMMITTED)]
    run = _run_with(steps)
    run.execute()
    run.state = MigState.IDLE
    run.execute()                       # resume: nothing re-runs
    assert calls == ["a", "b"]
    assert run.state == MigState.COMMITTED


def test_fault_point_fires_once_at_matching_occurrence():
    calls = []
    fp = FaultPoint("switch", 1, victims=[7])
    steps = [Step("switch:g0", "switch", lambda: calls.append("g0")),
             Step("switch:g1", "switch", lambda: calls.append("g1")),
             Step("commit", "commit", lambda: calls.append("c"))]
    run = _run_with(steps, fault=fp)
    with pytest.raises(MidSwitchFault) as ei:
        run.execute()
    assert ei.value.step == "switch:g1" and ei.value.victims == [7]
    assert calls == ["g0"]              # fired BEFORE the second switch
    assert run.journal[-1].step == "fault@switch:g1"
    run.execute()                       # fired latches: resume completes
    assert calls == ["g0", "g1", "c"]


def test_invalidate_reruns_exactly_the_dropped_steps():
    calls = []
    steps = [Step("p", "prepare", lambda: calls.append("p")),
             Step("s", "switch", lambda: calls.append("s"))]
    run = _run_with(steps).execute()
    run.invalidate("p", "nonexistent")
    run.execute()
    assert calls == ["p", "s", "p"]


def test_exec_counts_track_replays_and_invalidations():
    """The fuzz harness's journal invariant: a step body runs more than
    once ONLY if it was explicitly invalidated (rollback-discarded
    switches count as invalidated too)."""
    steps = [Step("p", "prepare", lambda: None),
             Step("s", "switch", lambda: None)]
    run = _run_with(steps).execute()
    assert run.exec_counts == {"p": 1, "s": 1}
    assert run.invalidated_log == set()
    run.invalidate("p", "never_ran")
    assert run.invalidated_log == {"p"}      # only steps that were done
    run.execute()
    assert run.exec_counts == {"p": 2, "s": 1}
    replayed = {n for n, c in run.exec_counts.items() if c > 1}
    assert replayed <= run.invalidated_log

    class _G:
        gid = "s"                            # step name "switch:s"
        members = []

    run2 = _run_with([Step("switch:s", "switch", lambda: None)]).execute()
    run2.record_switch(_G(), "plan")
    run2.rollback(lambda g, p: None, force=True)   # complete switchover
    assert run2.invalidated_log == {"switch:s"}


def _group(n=6, channels=2):
    g = CommGroup("dp.s0", "dp", list(range(n)), channels)
    g.establish_all()
    return g


def test_revert_delta_is_exact_inverse():
    g = _group()
    before = (list(g.members), dict(g.connections))
    plan = compute_delta_plan(g, {2: 10})
    apply_delta(g, plan)
    assert 10 in g.members
    revert_delta(g, plan)
    assert (g.members, g.connections) == before
    assert g.validate_rings()
    # the plan is re-staged so the re-switch needs no phase 1
    assert g.state == GroupState.READY_TO_SWITCHOUT
    assert g.pending_plan is plan


def test_rollback_reverts_only_partial_switches():
    reverted = []
    steps = [Step("switch:a", "switch", lambda: None),
             Step("switch:b", "switch", lambda: None)]

    class _G:
        def __init__(self, gid):
            self.gid = gid
            self.members = []

    ga, gb = _G("a"), _G("b")
    run = _run_with(steps)
    run.done.add("switch:a")
    run.record_switch(ga, "plan_a")
    # one of two switches done -> partial -> revert
    assert run.rollback(lambda g, p: reverted.append((g.gid, p))) == 1
    assert reverted == [("a", "plan_a")]
    assert "switch:a" not in run.done and not run.switched

    # both done -> complete switchover survives the fault
    run2 = _run_with(steps)
    run2.done |= {"switch:a", "switch:b"}
    run2.record_switch(ga, "pa")
    run2.record_switch(gb, "pb")
    assert run2.rollback(lambda g, p: reverted.append((g.gid, p))) == 0
    assert run2.done == {"switch:a", "switch:b"}
    # ...unless forced (a joiner died after its groups flipped)
    assert run2.rollback(lambda g, p: reverted.append((g.gid, p)),
                         force=True) == 2
    # reverse order: last switched reverts first
    assert reverted[-2:] == [("b", "pb"), ("a", "pa")]


# -------------------------------- slow: abort-at-every-journaled-step
CFG = campaign.CampaignCfg(warmup_iters=1, total_iters=3)

# every step kind the expected-migration journal contains, including
# both the nothing-switched (switch idx 0) and the partially-switched
# (switch idx 1 -> rollback) cases
ABORT_POINTS = [("prepare", 1), ("warmup", 0), ("barrier", 0),
                ("xfer", 0), ("switch", 0), ("switch", 1), ("swap", 0)]


@pytest.fixture(scope="module")
def reference():
    return campaign.reference_run(CFG)


@pytest.mark.slow
@pytest.mark.parametrize("kind,idx", ABORT_POINTS)
def test_abort_at_step_rolls_back_and_resumes_to_parity(kind, idx,
                                                        reference):
    ctl = campaign.build_controller(CFG, standby_count=1)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    leaver = ctl.engine.grid[(0, 1)]
    victim = ctl.engine.grid[(1, 0)]
    rep = ctl.expected_migration([leaver],
                                 inject=FaultPoint(kind, idx, [victim]))
    # the fault fired, was journaled, and the run resumed to commit
    assert rep.resumes == 1
    assert any(e.startswith("fault@") for e in rep.journal)
    assert ctl.last_run.state == MigState.COMMITTED
    # a partially-switched abort must journal the epoch rollback
    if (kind, idx) == ("switch", 1):
        assert any(e.startswith("revert:") for e in rep.journal)
    # async ledger drained to zero pending ops
    assert ctl.clock.pending_async() == 0
    # consistent epoch: every group active on live members, rings whole
    live = set(ctl.engine.grid.values())
    for g in ctl.engine.groups.values():
        assert g.state == GroupState.ACTIVE and g.pending_plan is None
        assert set(g.members) <= live
        assert g.validate_rings(), g.gid
    assert len(set(ctl.engine.epoch_signature().values())) == 1
    # neither victim nor leaver still trains; the retry converges
    assert victim not in live and leaver not in live
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert set(losses) == set(reference)
    assert all(losses[k] == reference[k] for k in reference), \
        "resumed migration must be bitwise transparent"


@pytest.mark.slow
def test_concurrent_second_failure_mid_switch(reference):
    """Two victims in different groups land between per-group
    switchovers; both recover off one abort/resume cycle."""
    ctl = campaign.build_controller(CFG, standby_count=2)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    leaver = ctl.engine.grid[(0, 1)]
    victims = [ctl.engine.grid[(1, 0)], ctl.engine.grid[(0, 0)]]
    rep = ctl.expected_migration([leaver],
                                 inject=FaultPoint("switch", 1, victims))
    assert rep.resumes == 1
    assert not ctl.standbys                  # both standbys promoted
    live = set(ctl.engine.grid.values())
    assert not any(v in live for v in victims)
    for g in ctl.engine.groups.values():
        assert g.state == GroupState.ACTIVE and g.validate_rings()
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert all(losses[k] == reference[k] for k in reference)


@pytest.mark.slow
def test_joiner_death_mid_switch_reships_state(reference):
    """Regression: the joiner itself dies between per-group
    switchovers. The run must force-revert, allocate a replacement,
    re-warm it, RE-SHIP the leaver's state (the first transfer died
    with the joiner) and resume to bitwise parity."""
    ctl = campaign.build_controller(CFG, standby_count=1)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    leaver = ctl.engine.grid[(0, 1)]
    joiner = ctl._alloc_joiners(1)[0]
    rep = ctl.expected_migration(
        [leaver], joiners=[joiner],
        inject=FaultPoint("switch", 1, [joiner]))
    assert rep.resumes == 1
    # state was transferred twice: once to the dead joiner, once to
    # its replacement — and the journal shows the second xfer
    assert rep.journal.count("xfer") == 2
    assert not ctl.cluster[joiner].alive
    replacement = rep.pairs[leaver]
    assert replacement != joiner
    assert replacement in ctl.engine.grid.values()
    for g in ctl.engine.groups.values():
        assert g.state == GroupState.ACTIVE and g.validate_rings()
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert all(losses[k] == reference[k] for k in reference)


@pytest.mark.slow
def test_elastic_recovery_mid_prepare_never_reuses_pending_joiner(
        reference):
    """Regression: joiners are reserved (PREPARING) at allocation. With
    no standby, a mid-prepare fault recovery allocates an elastic
    joiner — it must not be handed the machine already promised to the
    in-flight migration (which used to stay IDLE until warmup,
    double-assigning two grid slots to one machine)."""
    ctl = campaign.build_controller(CFG, standby_count=0)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    leaver = ctl.engine.grid[(0, 1)]
    victim = ctl.engine.grid[(1, 0)]
    rep = ctl.expected_migration([leaver],
                                 inject=FaultPoint("prepare", 1, [victim]))
    mids = list(ctl.engine.grid.values())
    assert len(mids) == len(set(mids)), \
        f"one machine assigned to two grid slots: {mids}"
    assert rep.pairs[leaver] in mids
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert all(losses[k] == reference[k] for k in reference)


@pytest.mark.slow
def test_k3_victim_set_joiner_standby_stayer(reference):
    """K=3 concurrent failures mid-switchover hitting three different
    role classes at once — the in-flight migration's joiner, a standby
    and a stayer — absorbed by ONE rollback-replan-resume cycle: the
    joiner is replaced and state re-shipped, the dead standby is
    replenished off the critical path, the stayer promotes the
    surviving standby, and the retry is bitwise transparent."""
    ctl = campaign.build_controller(CFG, standby_count=2)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    leaver = ctl.engine.grid[(0, 1)]
    joiner = ctl._alloc_joiners(1)[0]
    doomed_standby = ctl.standbys[-1]
    stayer = ctl.engine.grid[(1, 0)]
    rep = ctl.expected_migration(
        [leaver], joiners=[joiner],
        inject=FaultPoint("switch", 1, [joiner, doomed_standby, stayer]))
    assert rep.resumes == 1 and rep.ckpt_fallbacks == 0
    assert rep.journal.count("xfer") == 2         # re-ship to replacement
    live = set(ctl.engine.grid.values())
    assert len(live) == len(ctl.engine.grid)
    for v in (joiner, doomed_standby, stayer):
        assert v not in live and not ctl.cluster[v].alive
    assert leaver not in live and ctl.cluster[leaver].alive  # left, not died
    assert rep.pairs[leaver] in live and rep.pairs[leaver] != joiner
    # the dead standby was replaced off the critical path
    assert len(ctl.standbys) == 1
    assert all(ctl.cluster[s].alive for s in ctl.standbys)
    for g in ctl.engine.groups.values():
        assert g.state == GroupState.ACTIVE and g.validate_rings()
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert all(losses[k] == reference[k] for k in reference)


@pytest.mark.slow
def test_leaver_death_pre_xfer_dissolves_the_pair(reference):
    """The leaver itself dies during warmup, before its state shipped:
    the pair dissolves (reserved joiner back to the pool), the leaver
    recovers like any failed training machine, and the voided
    leaver-keyed steps are skipped on resume."""
    ctl = campaign.build_controller(CFG, standby_count=2)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    leaver = ctl.engine.grid[(0, 1)]
    stayer = ctl.engine.grid[(1, 0)]
    rep = ctl.expected_migration(
        [leaver], inject=FaultPoint("warmup", 0, [leaver, stayer]))
    assert rep.resumes == 1
    assert rep.pairs == {}                       # pair dissolved
    assert not ctl.cluster[leaver].alive
    live = set(ctl.engine.grid.values())
    assert leaver not in live and stayer not in live
    assert len(live) == len(ctl.engine.grid)
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert all(losses[k] == reference[k] for k in reference)


@pytest.mark.slow
def test_leaver_and_joiner_both_die_post_xfer(reference):
    """State shipped to the joiner, then BOTH ends of the pair die
    between per-group switchovers: the shipped bytes are gone with the
    joiner, so the benign-leaver shortcut must not fire — the leaver's
    slot recovers from checkpoint redundancy instead."""
    ctl = campaign.build_controller(CFG, standby_count=2)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    leaver = ctl.engine.grid[(0, 1)]
    joiner = ctl._alloc_joiners(1)[0]
    rep = ctl.expected_migration(
        [leaver], joiners=[joiner],
        inject=FaultPoint("switch", 1, [leaver, joiner]))
    assert rep.resumes == 1
    live = set(ctl.engine.grid.values())
    assert leaver not in live and joiner not in live
    assert len(live) == len(ctl.engine.grid)
    for g in ctl.engine.groups.values():
        assert g.state == GroupState.ACTIVE and g.validate_rings()
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert all(losses[k] == reference[k] for k in reference)


@pytest.mark.slow
def test_joiner_death_on_unexpected_path_repromotes(reference):
    """The promoted standby itself dies between the per-group
    switchovers of a failure recovery (the unexpected engine path —
    previously asserted out as unmodeled): the run force-reverts,
    re-promotes the next standby, re-restores state and resumes to
    bitwise parity."""
    ctl = campaign.build_controller(CFG, standby_count=2)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    failed = ctl.engine.grid[(0, 0)]
    promoted = ctl.standbys[0]
    survivor = ctl.standbys[1]
    rep = ctl.unexpected_failure(
        failed, inject=FaultPoint("switch", 1, [promoted]))
    assert rep.resumes == 1
    assert not ctl.cluster[promoted].alive
    assert rep.pairs == {failed: survivor}
    # promote/recover were re-executed after the invalidation
    assert ctl.last_run.exec_counts["promote"] == 2
    assert ctl.last_run.exec_counts["recover"] == 2
    assert survivor in ctl.engine.grid.values()
    for g in ctl.engine.groups.values():
        assert g.state == GroupState.ACTIVE and g.validate_rings()
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert all(losses[k] == reference[k] for k in reference)


@pytest.mark.slow
def test_standby_overflow_falls_back_to_ckpt_restart(reference):
    """Victims outnumber the standby pool with per-iteration
    checkpointing off: the overflow recovers via the checkpoint-restart
    baseline — ONE restart window, after which the remaining victims
    re-sync from the just-restored epoch — counted on the report, and
    the retry still reconverges bitwise (storage was saved at the
    injection step)."""
    ctl = campaign.build_controller(CFG, standby_count=1,
                                    per_iteration_ckpt=False)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    ctl.save_to_storage()
    leaver = ctl.engine.grid[(0, 1)]
    victims = [ctl.engine.grid[(1, 0)], ctl.engine.grid[(0, 0)],
               ctl.engine.grid[(1, 1)]]
    rep = ctl.expected_migration(
        [leaver], inject=FaultPoint("switch", 1, victims))
    assert rep.resumes == 1
    assert rep.ckpt_fallbacks == 1               # one restart window
    live = set(ctl.engine.grid.values())
    assert not (set(victims) | {leaver}) & live
    assert len(live) == len(ctl.engine.grid)
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert all(losses[k] == reference[k] for k in reference)


@pytest.mark.slow
def test_mid_switch_recovery_via_dp_peer_without_any_checkpoint(
        reference):
    """No standby, no per-iteration checkpoints, no storage save: a
    mid-switch victim with a live DP replica still recovers (elastic
    promotion + bitwise-identical peer state) instead of tripping the
    overflow fallback's storage assert."""
    ctl = campaign.build_controller(CFG, standby_count=0,
                                    per_iteration_ckpt=False)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    leaver = ctl.engine.grid[(0, 1)]
    victim = ctl.engine.grid[(1, 0)]        # DP peer d0s0 survives
    rep = ctl.expected_migration(
        [leaver], inject=FaultPoint("switch", 1, [victim]))
    assert rep.resumes == 1 and rep.ckpt_fallbacks == 0
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert all(losses[k] == reference[k] for k in reference)


@pytest.mark.slow
def test_reshard_recovery_keeps_machine_and_parity(reference):
    """Intra-machine re-sharding for a partial-GPU fault: the victim
    keeps its grid slot, the lost slices re-fetch from the DP replica,
    the flat buckets re-pack bitwise-identically, and the re-shard
    delta re-binds exactly the victim-adjacent QPs."""
    ctl = campaign.build_controller(CFG, standby_count=0)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    victim = ctl.engine.grid[(0, 0)]
    conns_before = {g.gid: dict(g.connections)
                    for g in ctl.engine.groups.values()}
    rep = ctl.gpu_fault(victim, policy="reshard")
    assert rep.kind == "gpu_reshard" and rep.resumes == 0
    assert rep.state_path == "dp_peer"
    m = ctl.cluster[victim]
    assert m.alive and m.failed_gpus == 1 and m.straggle_factor > 1.0
    assert victim in ctl.engine.grid.values()    # no migration happened
    for g in ctl.engine.groups.values():
        assert g.state == GroupState.ACTIVE and g.validate_rings()
        # membership and connection keys unchanged by the re-bind
        assert set(g.connections) == set(conns_before[g.gid])
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert all(losses[k] == reference[k] for k in reference)


@pytest.mark.slow
def test_reshard_run_survives_its_own_machine_dying(reference):
    """A fault inside the re-shard run kills the re-sharding machine
    itself: the recovery swaps a standby into its slot and the resumed
    run's remaining re-shard steps become no-ops (the replacement
    holds a whole, healthy shard) — no crash, bitwise parity."""
    ctl = campaign.build_controller(CFG, standby_count=1)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    victim = ctl.engine.grid[(0, 0)]
    rep = ctl.gpu_fault(victim, policy="reshard",
                        inject=FaultPoint("switch", 0, [victim]))
    assert rep.kind == "gpu_reshard" and rep.resumes == 1
    assert victim not in ctl.engine.grid.values()
    assert not ctl.cluster[victim].alive
    for g in ctl.engine.groups.values():
        assert g.state == GroupState.ACTIVE and g.validate_rings()
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert all(losses[k] == reference[k] for k in reference)


@pytest.mark.slow
def test_gpu_fault_auto_policy_picks_by_surviving_fraction(reference):
    """The PolicyEngine decision: any partial loss re-shards in place
    (the measured boundary — lost-fraction re-fetch always beats a
    fully-exposed whole-state ship), and only a machine with NOTHING
    surviving migrates away after all."""
    ctl = campaign.build_controller(CFG, standby_count=0)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    light = ctl.engine.grid[(0, 0)]
    rep1 = ctl.gpu_fault(light, policy="auto")          # 7/8 survive
    assert rep1.kind == "gpu_reshard"
    assert light in ctl.engine.grid.values()
    # 3/8 surviving used to hard-migrate under the old 0.5 threshold;
    # the corrected policy re-shards (above the 0.125 safety clamp,
    # and strictly cheaper on predicted AND measured downtime)
    partial = ctl.engine.grid[(1, 1)]
    rep_mid = ctl.gpu_fault(partial, policy="auto", lose=5)
    assert rep_mid.kind == "gpu_reshard"
    assert partial in ctl.engine.grid.values()
    heavy = ctl.engine.grid[(1, 0)]
    step0, nloss0 = ctl.engine.step_count, len(ctl.engine.losses)
    rep2 = ctl.gpu_fault(heavy, policy="auto",
                         lose=ctl.cluster[heavy].gpus)   # 0 survive
    # the iteration committed during the migrate-path prep lands in
    # the loss map too
    for i, st in enumerate(range(step0, ctl.engine.step_count)):
        losses[st] = ctl.engine.losses[nloss0 + i]
    assert rep2.kind == "gpu_degrade"
    assert heavy not in ctl.engine.grid.values()
    # every auto consultation left a journaled decision record
    pols = ctl.journal.replay()["policies"]
    assert [p["chosen"] for p in pols] == ["reshard", "reshard",
                                           "migrate"]
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert all(losses[k] == reference[k] for k in reference)


@pytest.mark.slow
def test_gpu_degrade_migrates_with_expected_downtime(reference):
    """A GPU-granular fault degrades one device; the machine keeps
    training during prep and leaves via the expected path."""
    ctl = campaign.build_controller(CFG, standby_count=0)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    victim = ctl.engine.grid[(0, 0)]
    step0, nloss0 = ctl.engine.step_count, len(ctl.engine.losses)
    rep = ctl.gpu_fault(victim)
    # the iteration committed during prep lands in the loss map too
    for i, st in enumerate(range(step0, ctl.engine.step_count)):
        losses[st] = ctl.engine.losses[nloss0 + i]
    assert rep.kind == "gpu_degrade"
    m = ctl.cluster[victim]
    assert m.failed_gpus == 1 and m.straggle_factor > 1.0
    assert m.alive                            # degraded, not dead
    assert victim not in ctl.engine.grid.values()
    # degraded machines never return to the job as joiners
    assert victim not in ctl._alloc_joiners(3)
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert all(losses[k] == reference[k] for k in reference)
