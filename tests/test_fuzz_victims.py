"""Victim-set recovery fuzz harness (slow).

50+ seeded draws, each arming a random FaultPoint step of an expected
migration with a random victim set — K <= 5, roles drawn from every
role class the runtime knows (pipeline stages, DP ranks, the standby
pool, the in-flight migration's joiner, and the leaver itself) — on
the real-exec engine. After every recovery the draw asserts:

- bitwise loss parity with an uninterrupted reference run;
- journal invariants: the run reaches COMMITTED off exactly one
  abort/resume cycle, every step executed, and NO step body ran twice
  unless the recovery explicitly invalidated it (done-step skipping is
  exact — `MigrationRun.exec_counts` vs `invalidated_log`);
- SimClock ledger conservation: zero pending async ops and, per
  channel, issued == exposed + hidden exactly;
- cluster consistency: no victim left in the grid, no machine in two
  grid slots, every comm group ACTIVE with whole rings, and a single
  committed epoch across the grid.

The model is deliberately tiny (layers=2, d=32) so the 50-draw sweep
stays within the nightly job's step timeout.
"""
import random

import pytest

from repro.core import campaign
from repro.core.groups import GroupState
from repro.core.migration import FaultPoint, MigState

FUZZ_CFG = campaign.CampaignCfg(
    layers=2, d_model=32, heads=2, vocab=64, global_batch=4,
    seq_len=16, micro_batches=1, warmup_iters=1, total_iters=3)

N_DRAWS = 52
SEED0 = 0xF00D

# every step kind the expected-migration journal contains; the fault
# fires immediately BEFORE the matching step, so ("xfer", 0) is still
# pre-transfer while ("switch", *) and ("swap", 0) are post-transfer
ABORT_POINTS = (("prepare", 0), ("prepare", 1), ("warmup", 0),
                ("barrier", 0), ("xfer", 0), ("switch", 0),
                ("switch", 1), ("swap", 0))
PRE_XFER_KINDS = {"prepare", "warmup", "barrier", "xfer"}

# the migration leaver is d0s1; stage/DP roles exclude it so "leaver"
# is the only way a draw kills the departing machine
ROLE_POOL = ("d0s0", "d1s0", "d1s1", "standby", "joiner", "leaver")

# every draw builds fresh controllers that compile the same tiny stage
# programs again; the SimClock charges the modeled compile constant
# (CampaignCfg.sim_compile_seconds), so loading them from the cache
# changes no assertion
pytestmark = pytest.mark.usefixtures("persistent_compile_cache")


@pytest.fixture(scope="module")
def reference():
    return campaign.reference_run(FUZZ_CFG)


def _draw_case(rng: random.Random):
    kind, idx = ABORT_POINTS[rng.randrange(len(ABORT_POINTS))]
    k = rng.randint(1, 5)
    roles = rng.sample(ROLE_POOL, k)
    return kind, idx, roles


def _assert_ledger_conserved(clock):
    assert clock.pending_async() == 0
    for ch, issued in clock.issued_by_channel.items():
        exposed = clock.exposed_by_channel.get(ch, 0.0)
        hidden = clock.hidden_by_channel.get(ch, 0.0)
        assert abs(issued - (exposed + hidden)) < 1e-9, \
            (ch, issued, exposed, hidden)


@pytest.mark.slow
@pytest.mark.parametrize("draw", range(N_DRAWS))
def test_random_victim_set_recovery(draw, reference):
    rng = random.Random(SEED0 + draw)
    kind, idx, roles = _draw_case(rng)
    # provision enough standbys for this victim set: one per training-
    # machine victim, one for the leaver (needed whenever its state
    # has not shipped to a live joiner — the pair dissolves and the
    # leaver recovers like a failed training machine), and one extra
    # when a standby itself dies so live ones remain for promotions
    n_train = sum(1 for r in roles if r.startswith("d"))
    needed = (n_train
              + (1 if "leaver" in roles else 0)
              + (1 if "standby" in roles else 0))
    ctl = campaign.build_controller(FUZZ_CFG, standby_count=max(needed, 1))
    losses = {0: ctl.engine.losses[0]}
    campaign._train_to(ctl, 1 + FUZZ_CFG.warmup_iters, losses)
    # a fresh storage checkpoint backstops the draws whose victim set
    # destroys every fast state source at once (e.g. a whole stage
    # plus the checkpoint-replica holders)
    ctl.save_to_storage()

    leaver = ctl.engine.grid[(0, 1)]
    joiners = ctl._alloc_joiners(1) if "joiner" in roles else None
    special = {"leaver": lambda: leaver,
               "joiner": lambda: joiners[0],
               "standby": lambda: ctl.standbys[-1]}
    victims = [special[r]() if r in special else campaign._victim(ctl, r)
               for r in roles]

    rep = ctl.expected_migration([leaver], joiners=joiners,
                                 inject=FaultPoint(kind, idx, victims))
    run = ctl.last_run

    # ---- journal invariants: one abort absorbed, done-step skipping
    # exact (a step body re-ran only if the recovery invalidated it)
    assert rep.resumes == 1, (kind, idx, roles)
    assert run.state == MigState.COMMITTED
    assert any(e.startswith("fault@") for e in rep.journal)
    executed_twice = {n for n, c in run.exec_counts.items() if c > 1}
    assert executed_twice <= run.invalidated_log, \
        f"steps replayed without invalidation: " \
        f"{executed_twice - run.invalidated_log} ({kind}@{idx}, {roles})"
    skippable = {s.name for s in run.steps} - run.invalidated_log
    assert all(run.exec_counts.get(n, 0) <= 1 for n in skippable)

    # ---- ledger conservation after the recovery settled
    _assert_ledger_conserved(ctl.clock)

    # ---- cluster consistency: victims gone, grid sane, rings whole
    mids = list(ctl.engine.grid.values())
    assert len(mids) == len(set(mids)), f"double-assigned grid: {mids}"
    live = set(mids)
    assert leaver not in live
    assert not (set(victims) & live), (victims, live)
    for v in victims:
        assert not ctl.cluster[v].alive
    for g in ctl.engine.groups.values():
        assert g.state == GroupState.ACTIVE and g.pending_plan is None
        assert set(g.members) <= live
        assert g.validate_rings(), g.gid
    assert len(set(ctl.engine.epoch_signature().values())) == 1

    # ---- bitwise parity with the uninterrupted reference
    campaign._train_to(ctl, 1 + FUZZ_CFG.total_iters, losses)
    _assert_ledger_conserved(ctl.clock)
    assert set(losses) == set(reference)
    assert all(losses[k] == reference[k] for k in reference), \
        f"victim-set recovery diverged ({kind}@{idx}, {roles})"
