"""Self-healing control plane: controller crash -> journal replay ->
worker re-registration -> run adoption.

The restarted controller is a FRESH instance rebuilt from the durable
ControlJournal alone: the standby ledger, storage index and in-flight
run step logs come from replay; the worker registry is rebuilt by
re-registration (never journaled); open runs resume from their last
journaled step with bitwise parity against an uninterrupted run."""
import pytest

from repro.cluster.node import NodeStatus
from repro.core import campaign
from repro.core.campaign import CampaignCfg, build_controller
from repro.core.journal import RECORD_TYPES
from repro.core.migration import ControllerCrash, CrashPoint, MigState

# the engine here charges the SimClock the modeled compile constant
# (CampaignCfg.sim_compile_seconds), so the stage programs that each
# fresh controller compiles again are loaded from a cache instead
pytestmark = pytest.mark.usefixtures("persistent_compile_cache")

CFG = CampaignCfg(warmup_iters=1, total_iters=4)


@pytest.fixture(scope="module")
def reference():
    return campaign.reference_run(CFG)


def _finish(ctl, losses, reference):
    campaign._train_to(ctl, 1 + CFG.total_iters, losses)
    assert set(losses) == set(reference)
    assert max(abs(losses[k] - reference[k]) for k in reference) == 0.0


def test_worker_registry_is_never_journaled():
    assert not any("worker" in t or "registry" in t for t in RECORD_TYPES)


def test_idle_restart_is_zero_downtime_and_preserves_ledgers(reference):
    ctl = build_controller(CFG, standby_count=1)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    ctl.save_to_storage()
    standbys0 = list(ctl.standbys)
    dt0 = ctl.clock.lane_total("downtime")

    ctl2 = ctl.restart()
    assert ctl2 is not ctl
    # no open run, nothing switching: the respawn + replay + worker
    # re-registration all overlap with training
    assert ctl2.clock.lane_total("downtime") == dt0
    assert ctl2.standbys == standbys0
    assert ctl2.storage is ctl.storage          # durable blobs survive
    assert set(ctl2.storage_coords) == set(ctl.storage_coords)
    assert any(p.name == "worker_reregister" for p in ctl2.clock.phases)
    _finish(ctl2, losses, reference)


def test_orphaned_preparing_reservation_released():
    ctl = build_controller(CFG, standby_count=1)
    campaign._train_to(ctl, 1 + CFG.warmup_iters, {})
    orphan = ctl._alloc_joiners(1)[0]           # reserved, never begun
    assert ctl.cluster[orphan].status == NodeStatus.PREPARING
    ctl2 = ctl.restart()
    assert ctl2.cluster[orphan].status == NodeStatus.IDLE


@pytest.mark.slow
def test_crash_mid_switchover_adopts_and_commits(reference):
    ctl = build_controller(CFG, standby_count=1)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    leaver = ctl.engine.grid[(0, CFG.pp - 1)]
    with pytest.raises(ControllerCrash):
        ctl.expected_migration([leaver], crash=CrashPoint("switch", 1))

    ctl2 = ctl.restart()
    # the open run was adopted and driven to COMMITTED
    assert len(ctl2.reports) == 1
    rep = ctl2.reports[0]
    assert rep.kind == "expected"
    run = ctl2.last_run
    assert run.state == MigState.COMMITTED
    # steps journaled as done before the crash were NOT re-executed on
    # the adopted instance (resume semantics, not replay-from-scratch)
    assert "barrier" not in run.exec_counts
    assert "xfer" not in run.exec_counts
    # the leaver is out of the grid, its joiner is in
    assert leaver not in ctl2.engine.grid.values()
    assert rep.pairs[leaver] in ctl2.engine.grid.values()
    # the journal agrees: every run record is committed
    state = ctl2.journal.replay()
    assert state["runs"] and all(r["committed"]
                                 for r in state["runs"].values())
    _finish(ctl2, losses, reference)


@pytest.mark.slow
def test_crash_mid_recovery_adopts_failure_run(reference):
    ctl = build_controller(CFG, standby_count=1)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    failed = ctl.engine.grid[(0, CFG.pp - 1)]
    with pytest.raises(ControllerCrash):
        ctl.unexpected_failure(failed, crash=CrashPoint("recover", 0))

    ctl2 = ctl.restart()
    assert len(ctl2.reports) == 1
    rep = ctl2.reports[0]
    assert rep.kind == "unexpected"
    assert rep.lost_iterations == 0
    # the standby consumed by the pre-crash promote step stayed
    # consumed across the restart (journaled inside promote)
    assert rep.pairs[failed] not in ctl2.standbys
    assert ctl2.last_run.state == MigState.COMMITTED
    # promote ran before the crash; adoption must not redo it
    assert "promote" not in ctl2.last_run.exec_counts
    _finish(ctl2, losses, reference)


@pytest.mark.slow
def test_victim_dies_while_control_plane_down(reference):
    """A data-plane machine fails while the controller is dead: the
    restarted controller's re-registration health check surfaces it and
    folds it into the adopted run as a synthetic mid-switch fault."""
    ctl = build_controller(CFG, standby_count=2)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    leaver = ctl.engine.grid[(0, CFG.pp - 1)]
    victim = ctl.engine.grid[(1, 0)]
    with pytest.raises(ControllerCrash):
        ctl.expected_migration([leaver], crash=CrashPoint("switch", 1))
    ctl.cluster[victim].fail()                  # dies while plane is down

    ctl2 = ctl.restart()
    # adoption absorbed the victim (nested standby recovery) and still
    # committed the original migration
    assert ctl2.last_run.state == MigState.COMMITTED
    assert ctl2.last_run.resumes >= 1
    assert victim not in ctl2.engine.grid.values()
    assert leaver not in ctl2.engine.grid.values()
    _finish(ctl2, losses, reference)


@pytest.mark.slow
def test_double_restart_is_idempotent(reference):
    """Restarting twice (the second time with no open runs) changes
    nothing: replay is idempotent end-to-end."""
    ctl = build_controller(CFG, standby_count=1)
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + CFG.warmup_iters, losses)
    leaver = ctl.engine.grid[(0, CFG.pp - 1)]
    with pytest.raises(ControllerCrash):
        ctl.expected_migration([leaver], crash=CrashPoint("prepare", 1))
    ctl2 = ctl.restart()
    assert ctl2.last_run.state == MigState.COMMITTED
    grid_after = dict(ctl2.engine.grid)
    standbys_after = list(ctl2.standbys)
    dt_after = ctl2.clock.lane_total("downtime")

    ctl3 = ctl2.restart()
    assert ctl3.engine.grid == grid_after
    assert ctl3.standbys == standbys_after
    assert ctl3.clock.lane_total("downtime") == dt_after
    assert not [r for r in ctl3.journal.replay()["runs"].values()
                if not r["committed"]]
    _finish(ctl3, losses, reference)
