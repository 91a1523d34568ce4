"""Real-execution DP x PP pipeline engine over cluster machines.

Each machine owns one pipeline stage of one data-parallel replica (TP is
intra-machine, below this engine's granularity). All cross-machine
traffic flows through the CommHooks seam (core/sandbox.py), so the same
step code runs in NORMAL, RECORD and REPLAY (sandboxed shadow-iteration)
modes — exactly the paper's PyTorch<->CCL interception point.

Stage programs are real jitted JAX functions; their AOT compile times
are measured wall-clock (XLA compilation is the cold-warmup analogue).
Every simulated machine's arrays live on JAX's default device.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.cluster.costmodel import CostModel, DEFAULT
from repro.cluster.node import Cluster, Machine, NodeStatus, Role
from repro.cluster.simclock import SimClock
from repro.core import flatbuf
from repro.core import groups as groups_mod
from repro.core import tracing
from repro.core.sandbox import CommHooks, CommMode, Tape
from repro.models import backbone, blocks
from repro.train import data as data_mod
from repro.train import optimizer as opt_mod
from repro.train.checkpoint import tree_bytes

FLOPS_PER_GPU = 125e12          # A100 bf16 at realistic MFU (sim charge)


class IterationInterrupt(Exception):
    """Raised by an armed interrupt hook at an iteration phase point.

    The aborted iteration commits nothing (step_count and the loss
    list only advance at the end of train_iteration); `dirty` is True
    when machine payloads were already mutated (post_reduce), so the
    recovery path must roll every stayer back to the last checkpoint
    before re-running the iteration."""

    def __init__(self, phase: str, it: int, victim: Optional[int] = None):
        super().__init__(f"iteration {it} interrupted at {phase}")
        self.phase = phase
        self.it = it
        self.victim = victim
        self.dirty = phase == "post_reduce"


def stage_role_key(stage: int) -> int:
    return stage


def stage_type(stage: int, pp: int) -> str:
    if pp == 1:
        return "only"
    if stage == 0:
        return "first"
    if stage == pp - 1:
        return "last"
    return "middle"


# ---------------------------------------------------------------- stages
def split_stage_params(full_params: dict, stage: int, pp: int,
                       cfg: ArchConfig) -> dict:
    """Contiguous layer split; stage 0 carries the embedding, the last
    stage carries final_ln + head."""
    L = cfg.num_layers
    assert len(cfg.block_pattern) == 1, "engine supports period-1 archs"
    assert L % pp == 0, (L, pp)
    per = L // pp
    lo, hi = stage * per, (stage + 1) * per
    sl = jax.tree.map(lambda x: x[lo:hi], full_params["stack"]["scan"])
    p = {"stack": {"scan": sl, "tail": ()}}
    if stage == 0:
        p["embed"] = full_params["embed"]
    if stage == pp - 1:
        p["final_ln"] = full_params["final_ln"]
        p["head"] = (full_params["head"] if "head" in full_params
                     else full_params["embed"].T)
    return p


def make_stage_fns(cfg: ArchConfig, stage: int, pp: int):
    """Pure stage programs (unjitted): fwd / bwd / loss_bwd / update."""
    first, last = stage == 0, stage == pp - 1

    def fwd(params, x_or_tokens):
        if first:
            x = params["embed"][x_or_tokens]
        else:
            x = x_or_tokens
        x, _ = backbone.apply_stack(params["stack"], x, cfg, 1, None,
                                    positions=_positions(x, x_or_tokens,
                                                         first),
                                    impl="dense", remat=False)
        return x

    def _positions(x, tok, is_first):
        B = (tok if is_first else x).shape[0]
        S = (tok if is_first else x).shape[1]
        return jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def head_loss(params, x, tokens):
        x = blocks.rmsnorm(x, params["final_ln"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"]) \
            .astype(jnp.float32)
        return backbone.lm_loss(logits, tokens)

    def stage_loss(params, x_or_tokens, tokens):
        y = fwd(params, x_or_tokens)
        return head_loss(params, y, tokens)

    def last_bwd(params, x_or_tokens, tokens):
        loss, (dp_, dx) = jax.value_and_grad(stage_loss, argnums=(0, 1))(
            params, x_or_tokens, tokens)
        return loss, dp_, dx

    def mid_bwd(params, x_or_tokens, dy):
        y, pull = jax.vjp(fwd, params, x_or_tokens)
        dp_, dx = pull(dy)
        return dp_, dx

    return {"fwd": fwd, "last_bwd": last_bwd, "mid_bwd": mid_bwd}


def make_flat_update(spec: flatbuf.SegmentedSpec, adam: opt_mod.AdamCfg):
    """The flat update program (unjitted): average the reduced gradient
    buckets, then one fully-flat Adam step."""

    def upd_flat(seg_grads, opt, n_avg):
        # average in the bucket's own dtype (bf16 stays bf16 — jnp
        # would otherwise promote against the f32 scalar); the
        # per-leaf reference path divides identically
        segs = tuple(g / n_avg.astype(g.dtype) for g in seg_grads)
        return opt_mod.adam_update_flat(spec, segs, opt, adam)

    return upd_flat


# ---------------------------------------------------------------- engine
@dataclass
class CompiledRole:
    fns: Dict[str, Any]
    compile_seconds: float


class PipelineEngine:
    def __init__(self, cfg: ArchConfig, dp: int, pp: int,
                 global_batch: int, seq_len: int, cluster: Cluster,
                 clock: SimClock, comm: CommHooks,
                 cost: CostModel = DEFAULT, micro_batches: int = 2,
                 seed: int = 0,
                 adam: Optional[opt_mod.AdamCfg] = None,
                 use_flat_buffers: bool = True,
                 param_dtype=jnp.float32,
                 sim_compile_seconds: Optional[float] = None):
        assert global_batch % (dp * micro_batches) == 0
        self.cfg, self.dp, self.pp = cfg, dp, pp
        self.global_batch, self.seq_len = global_batch, seq_len
        self.nmb = micro_batches
        self.mb_size = global_batch // dp // micro_batches
        self.cluster, self.clock, self.comm, self.cost = \
            cluster, clock, comm, cost
        self.adam = adam or opt_mod.AdamCfg(lr=1e-3, warmup_steps=10)
        self.seed = seed
        # Flat-buffer hot path: per-stage contiguous per-dtype gradient
        # buckets, ONE async all-reduce per bucket issued as soon as the
        # stage's grads are accumulated (exposed remainder charged at
        # wait), a fully-flat Adam state, and ONE update broadcast to
        # the DP replicas. False keeps the per-leaf reference path
        # (numerics-parity tests and the before/after benchmark).
        self.use_flat_buffers = use_flat_buffers
        # Mixed precision: stack (transformer block) weights are cast
        # to param_dtype; embeddings / final norm / head stay fp32, so
        # param_dtype=bf16 produces genuinely mixed-dtype stages whose
        # grads need per-dtype segment buckets.
        self.param_dtype = jnp.dtype(param_dtype)
        self.grid: Dict[Tuple[int, int], int] = {}
        self._coords: Dict[int, Tuple[int, int]] = {}
        # Degraded-mode rank hosting (dp_retire/dp_restaff): logical
        # (d, s) slots whose machine was retired, mapped to the
        # surviving same-stage DP replica that stands in for them. The
        # LOGICAL grid shape (dp, mb_size, navg, the bucket reduce)
        # never changes — DP replicas hold bitwise-identical state, so
        # a host serves a retired rank with its own payload and the
        # math stays exactly the reference math; only throughput
        # degrades (the host runs the stage compute once per hosted
        # rank) and the physical comm rings shrink.
        self.hosted: Dict[Tuple[int, int], int] = {}
        self._flat_specs: Dict[int, flatbuf.SegmentedSpec] = {}
        self._state_specs: Dict[int, flatbuf.ByteSpec] = {}
        self._grad_bytes: Dict[int, int] = {}
        self._bucket_reduce: Dict[int, Any] = {}
        self._param_rebuild: Dict[int, Any] = {}
        # stage -> (bucket tuple, materialized params): DP replicas
        # share the broadcast buckets, so they share the unflatten too
        self._mat_cache: Dict[int, Tuple[Any, Any]] = {}
        self._batch_cache: Tuple[int, Optional[np.ndarray]] = (-1, None)
        self.groups: Dict[str, groups_mod.CommGroup] = {}
        self.stream = data_mod.SyntheticStream(
            data_mod.DataCfg(cfg.vocab_size, global_batch, seq_len,
                             seed=seed + 77))
        self._role_cache: Dict[int, CompiledRole] = {}
        # Deterministic-simulation mode: when set, every clock charge
        # that would otherwise use a *measured* wall-clock duration
        # (XLA compiles, shadow-iteration execution) uses this modeled
        # constant instead. Campaign runs set it so repeated runs emit
        # byte-identical downtime ledgers; None keeps the measured
        # charges (the CPU-measurable warm-up benefit).
        self.sim_compile_seconds = sim_compile_seconds
        # phase -> callback(engine, phase, it), invoked at named points
        # inside train_iteration ("pre_reduce": fwd/bwd done, grads not
        # yet reduced; "post_reduce": update applied, iteration not yet
        # committed). A callback may raise IterationInterrupt to model
        # a mid-iteration failure; Controller.interrupt_iteration owns
        # the recovery choreography.
        self.interrupt_hooks: Dict[str, Any] = {}
        self.step_count = 0
        self.losses: List[float] = []
        self._stage_flops = self._estimate_stage_flops()

    # ------------------------------------------------------------ setup
    def setup(self, machine_ids: List[int]) -> None:
        assert len(machine_ids) >= self.dp * self.pp
        # re-setup must not leave stale mid -> (d, s) entries behind:
        # coords_of would silently serve coordinates for evicted mids
        self.grid.clear()
        self._coords.clear()
        self.hosted.clear()
        full = backbone.init_params(self.cfg, jax.random.PRNGKey(self.seed),
                                    tp=1, dtype=jnp.float32)
        it = iter(machine_ids)
        for d in range(self.dp):
            for s in range(self.pp):
                mid = next(it)
                self.grid[(d, s)] = mid
                self._coords[mid] = (d, s)
                m = self.cluster[mid]
                m.status = NodeStatus.TRAINING
                m.role = Role(d, s, self.pp)
                params = self._cast_stage_params(
                    split_stage_params(full, s, self.pp, self.cfg))
                params = jax.tree.map(jnp.asarray, params)
                if self.use_flat_buffers:
                    spec = self.flat_spec(s)
                    m.payload = {
                        "params": params,
                        "param_segs": spec.flatten(params),
                        "_seg_stage": s,
                        "opt": opt_mod.init_flat_opt_state(spec, params),
                        "step": 0}
                else:
                    m.payload = {"params": params,
                                 "opt": opt_mod.init_opt_state(params),
                                 "step": 0}
                m.device.alloc(tree_bytes({"params": params,
                                           "opt": m.payload["opt"],
                                           "step": 0}), "train_state",
                               self.clock.now)
                m.device.alloc(self.grad_buffer_bytes(s), "grad_buffer",
                               self.clock.now)
        self.groups = groups_mod.build_groups(
            self.dp, self.pp, self.grid, channels=self.cost.channels_per_group)
        for g in self.groups.values():
            g.establish_all()

    def _mid(self, d: int, s: int) -> int:
        """Physical machine serving logical rank (d, s): the grid entry,
        or — for a retired slot — its same-stage host. Explicit `in`
        check because machine id 0 is falsy."""
        key = (d, s)
        if key in self.grid:
            return self.grid[key]
        return self.hosted[key]

    def machine(self, d: int, s: int) -> Machine:
        return self.cluster[self._mid(d, s)]

    def coords_of(self, mid: int) -> Tuple[int, int]:
        """O(1) reverse lookup, kept in sync by setup/swap_machine."""
        try:
            return self._coords[mid]
        except KeyError:
            raise KeyError(mid) from None

    def _estimate_stage_flops(self) -> float:
        cfg = self.cfg
        per_layer = (12 * cfg.d_model ** 2 +
                     2 * cfg.d_model * cfg.d_ff * 3)
        tokens = self.mb_size * self.seq_len
        return 3 * per_layer * (cfg.num_layers / self.pp) * tokens

    # --------------------------------------------------------- compiling
    def _cast_stage_params(self, params: dict) -> dict:
        """Mixed-precision cast: stack weights to param_dtype, the
        embedding / final norm / head stay fp32."""
        if self.param_dtype == jnp.float32:
            return params
        out = dict(params)
        out["stack"] = jax.tree.map(
            lambda x: x.astype(self.param_dtype), params["stack"])
        return out

    def _stage_param_spec(self, stage: int):
        """ShapeDtypeStruct pytree of this stage's params (no data)."""
        return jax.eval_shape(
            lambda k: self._cast_stage_params(split_stage_params(
                backbone.init_params(self.cfg, k, tp=1,
                                     dtype=jnp.float32),
                stage, self.pp, self.cfg)),
            jax.ShapeDtypeStruct((2,), jnp.uint32))

    def flat_spec(self, stage: int) -> flatbuf.SegmentedSpec:
        """Gradient-bucket layout for a stage: one contiguous bucket
        per dtype (derivable without setup, so joiners/standbys can
        build buckets for roles they never held)."""
        if stage not in self._flat_specs:
            self._flat_specs[stage] = flatbuf.SegmentedSpec.from_tree(
                self._stage_param_spec(stage))
        return self._flat_specs[stage]

    def grad_buffer_bytes(self, stage: int) -> int:
        """Gradient-buffer footprint for a stage."""
        if self.use_flat_buffers:
            return self.flat_spec(stage).nbytes
        if stage not in self._grad_bytes:
            self._grad_bytes[stage] = flatbuf.ByteSpec.from_tree(
                self._stage_param_spec(stage)).nbytes
        return self._grad_bytes[stage]

    def bucket_reduce_fn(self, stage: int):
        """The whole DP reduction as ONE fused program: per-replica
        bucket drains and the cross-replica sum collapse into a single
        pass (XLA fuses the adds into the concat's output writes),
        mirroring how a CCL reduces in transport.  Returns the reduced
        per-dtype segment buffers.  Compiled lazily and cached OUTSIDE
        compile_role so shadow/standby fresh compiles — which never run
        it — don't get its compile time charged to the downtime lane."""
        if stage not in self._bucket_reduce:
            spec = self.flat_spec(stage)
            pspec = self._stage_param_spec(stage)

            def bucket_reduce(*trees):
                # leafwise adds first, ONE drain into the buckets after
                # (same add order elementwise, so bitwise-identical to
                # reducing the buckets — but XLA emits one copy per
                # leaf instead of re-laying-out every replica's tree)
                acc = trees[0]
                for t in trees[1:]:
                    acc = jax.tree.map(jnp.add, acc, t)
                return spec.flatten(acc)

            self._bucket_reduce[stage] = jax.jit(bucket_reduce).lower(
                *([pspec] * self.dp)).compile()
        return self._bucket_reduce[stage]

    def compile_role(self, stage: int, fresh: bool = False,
                     charge: Optional[str] = None) -> CompiledRole:
        """AOT-compile the stage programs. fresh=True bypasses the
        engine cache (a cold machine compiling from scratch)."""
        if not fresh and stage in self._role_cache:
            return self._role_cache[stage]
        with tracing.span("tm:compile_role", stage=stage):
            cfg = self.cfg
            fns = make_stage_fns(cfg, stage, self.pp)
            B, S = self.mb_size, self.seq_len
            tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
            act = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.float32)
            pspec = self._stage_param_spec(stage)
            x_in = tok if stage == 0 else act
            t0 = time.perf_counter()
            out = {}
            out["fwd"] = jax.jit(fns["fwd"]).lower(pspec, x_in).compile()
            if stage == self.pp - 1:
                out["last_bwd"] = jax.jit(fns["last_bwd"]) \
                    .lower(pspec, x_in, tok).compile()
            else:
                out["mid_bwd"] = jax.jit(fns["mid_bwd"]) \
                    .lower(pspec, x_in, act).compile()

            navg_spec = jax.ShapeDtypeStruct((), jnp.float32)
            if self.use_flat_buffers:
                spec = self.flat_spec(stage)
                seg_specs = tuple(jax.ShapeDtypeStruct((g.size,), g.dtype)
                                  for g in spec.segments)
                # drain a replica's accumulated grad tree into its
                # per-dtype buckets (one program; on real accelerators XLA
                # writes the grads straight into the bucket layout)
                out["flatten"] = jax.jit(spec.flatten).lower(pspec).compile()
                # params materialize from the buckets only at the fwd/bwd
                # boundary (leavers ship the buckets without ever paying
                # this)
                out["unflatten"] = jax.jit(spec.unflatten).lower(
                    seg_specs).compile()
                ospec = jax.eval_shape(
                    lambda p: opt_mod.init_flat_opt_state(spec, p), pspec)
                out["update"] = jax.jit(
                    make_flat_update(spec, self.adam)).lower(
                        seg_specs, ospec, navg_spec).compile()
            else:
                ospec = jax.eval_shape(opt_mod.init_opt_state, pspec)

                def upd(grads, opt, n_avg):
                    g = jax.tree.map(lambda x: x / n_avg.astype(x.dtype),
                                     grads)
                    return opt_mod.adam_update(g, opt, self.adam,
                                               param_dtype=None)

                out["update"] = jax.jit(upd).lower(
                    pspec, ospec, navg_spec).compile()
            dt = time.perf_counter() - t0
        role = CompiledRole(out, dt)
        if not fresh:
            self._role_cache[stage] = role
        if charge is not None:
            self.clock.advance(self.compile_charge(role), f"jit:{stage}",
                               lane=charge)
        return role

    def compile_charge(self, role: CompiledRole,
                       exec_seconds: float = 0.0) -> float:
        """Seconds to charge the clock for compiling (and optionally
        shadow-executing) a role: the measured wall-clock by default,
        the modeled constant in deterministic-simulation mode."""
        if self.sim_compile_seconds is not None:
            return self.sim_compile_seconds
        return role.compile_seconds + exec_seconds

    # ----------------------------------------------------------- running
    def _phase_point(self, phase: str, it: int) -> None:
        """Named checkpoint inside train_iteration where an armed
        interrupt hook can raise (fault-injection seam)."""
        cb = self.interrupt_hooks.get(phase)
        if cb is not None:
            cb(self, phase, it)

    INTERRUPT_PHASES = ("pre_reduce", "post_reduce")

    def arm_interrupt(self, phase: str, victim: int) -> None:
        """One-shot: raise IterationInterrupt for `victim` the next
        time the iteration reaches `phase`."""
        assert phase in self.INTERRUPT_PHASES, phase

        def fire(engine, ph, it):
            engine.interrupt_hooks.pop(ph, None)
            raise IterationInterrupt(ph, it, victim)

        self.interrupt_hooks[phase] = fire

    def _mb_tokens(self, it: int, d: int, mb: int) -> jnp.ndarray:
        # one SyntheticStream materialization per iteration, not dp*nmb
        if self._batch_cache[0] != it:
            self._batch_cache = (it, self.stream.batch(it)["tokens"])
        batch = self._batch_cache[1]
        per_d = batch.shape[0] // self.dp
        chunk = batch[d * per_d:(d + 1) * per_d]
        return jnp.asarray(chunk[mb * self.mb_size:(mb + 1) * self.mb_size])

    def _stage_params(self, m: Machine):
        """A machine's live params, materialized lazily from its flat
        segment buffers at the fwd/bwd boundary (leavers never pay
        this). The update broadcasts ONE bucket tuple to every DP
        replica, so materialization is cached per stage by bucket
        identity — one jitted unflatten per stage per iteration, not
        one per replica."""
        # Memory model: the materialized tree is treated as ALIASING
        # the buckets (on real hardware the unflatten is a view over
        # the flat storage, which is the point of the flat layout), so
        # the device ledger charges the state bytes once — the CPU-side
        # copy jax makes here is a simulation artifact, not a modeled
        # allocation.
        p = m.payload.get("params")
        if p is None:
            s = m.payload["_seg_stage"]
            segs = m.payload["param_segs"]
            cached = self._mat_cache.get(s)
            if cached is not None and cached[0] is segs:
                p = cached[1]
            else:
                p = self.compile_role(s).fns["unflatten"](tuple(segs))
                self._mat_cache[s] = (segs, p)
            m.payload["params"] = p
        return p

    def train_iteration(self, it: Optional[int] = None,
                        lane: str = "train") -> float:
        """One synchronous iteration across the whole grid.

        On the flat path, communication is overlap-aware: p2p
        activation/grad transfers are issued onto their link's ledger
        channel as the dataflow reaches them; each stage's gradbucket
        all-reduce is issued as soon as the stage's grads are
        accumulated (the final-microbatch backward wave is charged per
        stage, earlier stages' backward hiding later stages'
        in-flight reductions); waits charge only the exposed
        remainder, and the iteration barrier settles any leftovers.

        Ledger contract: with sim_compile_seconds set, every clock
        charge in here (and in shadow/warmup/state transfer) must stay
        a deterministic function of (config, CostModel, byte sizes) —
        never of tensor values — because core/simexec.py mirrors the
        exact charge sequence tensor-free and tests pin the two
        ledgers bit-for-bit (tests/test_simexec.py)."""
        with tracing.span("tm:train_iteration"):
            it = self.step_count if it is None else it
            comm = self.comm
            comm.reset_counters()
            losses = []
            grads_acc: Dict[Tuple[int, int], Any] = {}
            # compute-time charge (simulated cluster time): the critical
            # machine is the slowest of (straggle factor x hosted-rank
            # load) — a degraded-mode host runs its stage compute once per
            # rank it serves, so hosting shows up as throughput, never as
            # different math
            load: Dict[int, int] = {}
            for d in range(self.dp):
                for s in range(self.pp):
                    mid = self._mid(d, s)
                    load[mid] = load.get(mid, 0) + 1
            slow = max(self.cluster[mid].straggle_factor * n
                       for mid, n in load.items())
            t_comp = 3 * self._stage_flops * self.nmb * slow / \
                (FLOPS_PER_GPU * self.cluster[self._mid(0, 0)].gpus)
            overlap = self.use_flat_buffers
            if not overlap:
                self.clock.advance(t_comp, "compute", lane=lane)

            for d in range(self.dp):
                acts: Dict[Tuple[int, int], Any] = {}
                for mb in range(self.nmb):
                    tokens = self._mb_tokens(it, d, mb)
                    x = tokens
                    for s in range(self.pp):
                        m = self.machine(d, s)
                        fns = self.compile_role(s).fns
                        if s > 0:
                            x = comm.p2p_recv(stage_role_key(s), "act",
                                              src=self._mid(d, s - 1),
                                              dst=m.mid, value=x,
                                              overlap=overlap)
                        acts[(s, mb)] = x
                        if s < self.pp - 1:
                            y = fns["fwd"](self._stage_params(m), x)
                            comm.p2p_send(stage_role_key(s), "act", m.mid,
                                          self._mid(d, s + 1), y)
                            x = y
                    # backward
                    dy = None
                    for s in reversed(range(self.pp)):
                        m = self.machine(d, s)
                        fns = self.compile_role(s).fns
                        if s == self.pp - 1:
                            loss, dp_, dx = fns["last_bwd"](
                                self._stage_params(m), acts[(s, mb)], tokens)
                            with tracing.span("tm:loss_sync"):
                                losses.append(float(loss))
                        else:
                            dy = comm.p2p_recv(stage_role_key(s), "grad",
                                               src=self._mid(d, s + 1),
                                               dst=m.mid, value=dy,
                                               overlap=overlap)
                            dp_, dx = fns["mid_bwd"](self._stage_params(m),
                                                     acts[(s, mb)], dy)
                        if s > 0:
                            comm.p2p_send(stage_role_key(s), "grad", m.mid,
                                          self._mid(d, s - 1), dx)
                            dy = dx
                        key = (d, s)
                        grads_acc[key] = dp_ if key not in grads_acc else \
                            jax.tree.map(jnp.add, grads_acc[key], dp_)

            # DP gradient all-reduce per stage + update
            self._phase_point("pre_reduce", it)
            navg = jnp.asarray(float(self.dp * self.nmb), jnp.float32)
            with tracing.span("tm:reduce_update"):
                if self.use_flat_buffers:
                    self._flat_reduce_and_update(grads_acc, navg, it, t_comp,
                                                 lane)
                else:
                    self._leaf_reduce_and_update(grads_acc, navg, it)
            self._phase_point("post_reduce", it)
            self.comm.barrier("iter")
            self.step_count = it + 1
            loss = float(np.mean(losses))
            self.losses.append(loss)
            return loss

    def _flat_reduce_and_update(self, grads_acc, navg, it: int,
                                t_comp: float, lane: str) -> None:
        """Overlapped bucketed reduction + fully-flat Adam update.

        Compute is charged in two parts: the bulk of the iteration
        first (the in-flight p2p traffic hides under it), then the
        final microbatch's backward wave stage by stage — issuing
        stage s's bucket collectives right after its slice, so they
        progress while stages s-1..0 still run backward. The update is
        computed once per stage and the flat result broadcast to every
        DP replica; params stay as buckets until the next fwd touches
        them."""
        # final-microbatch backward wave: one slice per stage (bwd is
        # ~2/3 of a microbatch's fwd+bwd compute), clamped so the tail
        # never exceeds the whole iteration's budget
        t_bwd = min((2.0 / 3.0) * t_comp / self.nmb, t_comp / self.pp)
        self.clock.advance(max(t_comp - self.pp * t_bwd, 0.0),
                           "compute", lane=lane)
        handles: Dict[int, List[Any]] = {}
        for s in reversed(range(self.pp)):
            self.clock.advance(t_bwd, f"compute:bwd_tail:{s}", lane=lane)
            stacked = [grads_acc[(d, s)] for d in range(self.dp)]
            segs = self.bucket_reduce_fn(s)(*stacked)
            # the ring cost scales with the PHYSICAL participant count:
            # hosted ranks contribute no extra ring hop (their grads
            # already live on the host), which is the comm upside of a
            # degraded-mode shrink
            phys = len({self._mid(d, s) for d in range(self.dp)})
            handles[s] = [
                self.comm.all_reduce_async(stage_role_key(s),
                                           "gradbucket", [seg],
                                           participants=phys)
                for seg in segs]
        for s in reversed(range(self.pp)):       # wait in issue order
            fns = self.compile_role(s).fns
            reduced = tuple(self.comm.wait(h) for h in handles[s])
            new_segs, new_opt, _ = fns["update"](
                reduced, self.machine(0, s).payload["opt"], navg)
            for d in range(self.dp):
                m = self.machine(d, s)
                m.payload["param_segs"] = new_segs
                m.payload["params"] = None      # lazy: next fwd/bwd
                m.payload["_seg_stage"] = s
                m.payload["opt"] = new_opt
                m.payload["step"] = it + 1

    def _leaf_reduce_and_update(self, grads_acc, navg, it: int) -> None:
        """Per-leaf reference path: one all_reduce per leaf, one Adam
        update per DP rank (kept for bitwise parity testing)."""
        for s in range(self.pp):
            stacked = [grads_acc[(d, s)] for d in range(self.dp)]
            fns = self.compile_role(s).fns
            leaves0, tdef = jax.tree.flatten(stacked[0])
            reduced_leaves = []
            for li in range(len(leaves0)):
                arrs = [jax.tree.leaves(stacked[d])[li]
                        for d in range(self.dp)]
                red = self.comm.all_reduce(stage_role_key(s),
                                           f"grad{li}", arrs)
                reduced_leaves.append(red)
            reduced = jax.tree.unflatten(tdef, reduced_leaves)
            for d in range(self.dp):
                m = self.machine(d, s)
                new_p, new_opt, _ = fns["update"](reduced,
                                                  m.payload["opt"], navg)
                m.payload["params"] = new_p
                m.payload["opt"] = new_opt
                m.payload["step"] = it + 1

    # ---------------------------------------------------- record / replay
    def record_iteration(self, it: Optional[int] = None) -> Tape:
        """First-iteration pre-record (§4.2): run one normal iteration
        with the recording hook attached, then alias stage tapes onto
        the three general-standby role types."""
        prev = self.comm.mode
        self.comm.mode = CommMode.RECORD
        self.train_iteration(it)
        self.comm.mode = prev
        tape = self.comm.tape
        # a shadow iteration replays exactly one microbatch, so the
        # per-(replica, microbatch) p2p recordings collapse: middle
        # stages fuse act+grad into one 'io' entry (one replay recv
        # instead of two), first/last keep only the first entry per tag
        freed, fused = 0, 0
        for s in range(self.pp):
            rk = stage_role_key(s)
            df = tape.fuse_p2p_io(rk)
            if df >= 0:
                fused += 1
                freed += df
            else:
                freed += tape.coalesce_p2p(rk)
        tape.meta["p2p_fused_roles"] = fused
        tape.meta["p2p_bytes_freed"] = freed
        reps = {"first": 0, "last": self.pp - 1,
                "middle": 1 if self.pp > 2 else 0,
                "only": 0}
        for role_type in (("only",) if self.pp == 1
                          else ("first", "middle", "last")):
            tape.alias_role(stage_role_key(reps[role_type]), role_type)
        tape.meta["pp"] = self.pp
        tape.meta["recorded_step"] = self.step_count - 1
        return tape

    def shadow_iteration(self, machine: Machine, role_key,
                         stage: int, state: Optional[dict] = None,
                         lane: str = "overlap",
                         fresh_compile: bool = True) -> CompiledRole:
        """Sandboxed shadow iteration on a joiner/standby (§4.2 replay).

        Compiles the role's programs (REAL XLA compile, measured) and
        executes one isolated iteration fed from the tape. Returns the
        compiled role; the machine's warm_roles cache is populated."""
        with tracing.span("tm:shadow_iteration"):
            prev_mode, prev_members = self.comm.mode, self.comm.sandbox_members
            self.comm.mode = CommMode.REPLAY
            self.comm.sandbox_members = {machine.mid}
            self.comm.reset_counters()
            try:
                role = self.compile_role(stage, fresh=fresh_compile)
                # machine state for the shadow run: checkpoint pull or zeros
                if state is None:
                    full = backbone.init_params(
                        self.cfg, jax.random.PRNGKey(self.seed), tp=1,
                        dtype=jnp.float32)
                    params = jax.tree.map(
                        jnp.asarray,
                        self._cast_stage_params(split_stage_params(
                            full, stage, self.pp, self.cfg)))
                    opt = (opt_mod.init_flat_opt_state(self.flat_spec(stage),
                                                       params)
                           if self.use_flat_buffers
                           else opt_mod.init_opt_state(params))
                    state = {"params": params, "opt": opt, "step": 0}
                t0 = time.perf_counter()
                tokens = self._mb_tokens(0, 0, 0)
                # middle stages replay ONE fused act+grad entry when the
                # record step coalesced the tape (first/last have only one
                # direction recorded, so they keep the per-tag entry)
                fused = self.comm.tape.has((role_key, "p2p", "io", 0))
                io = (self.comm.p2p_recv(role_key, "io", src=-1,
                                         dst=machine.mid, value=None)
                      if fused else None)
                if stage == 0:
                    x = tokens
                else:
                    x = io[0] if fused else self.comm.p2p_recv(
                        role_key, "act", src=-1, dst=machine.mid, value=None)
                if stage == self.pp - 1:
                    _, dp_, _ = role.fns["last_bwd"](state["params"], x,
                                                     tokens)
                else:
                    y = role.fns["fwd"](state["params"], x)
                    dy = io[1] if fused else self.comm.p2p_recv(
                        role_key, "grad", src=-1, dst=machine.mid, value=None)
                    dp_, _ = role.fns["mid_bwd"](state["params"], x, dy)
                navg = jnp.asarray(float(self.dp * self.nmb), jnp.float32)
                if self.use_flat_buffers:
                    # per-dtype bucket entries replayed from the tape, not
                    # per-leaf (same keys the async issue wrote)
                    buckets = role.fns["flatten"](dp_)
                    reduced = tuple(
                        self.comm.all_reduce(role_key, "gradbucket", [b])
                        for b in buckets)
                else:
                    leaves = jax.tree.leaves(dp_)
                    red = [self.comm.all_reduce(role_key, f"grad{i}", [g])
                           for i, g in enumerate(leaves)]
                    reduced = jax.tree.unflatten(jax.tree.structure(dp_), red)
                role.fns["update"](reduced, state["opt"], navg)
                shadow_exec = time.perf_counter() - t0
                machine.warm_roles[role_key] = role
                machine.payload.setdefault("sandbox_state", state)
                self.clock.advance(self.compile_charge(role, shadow_exec),
                                   f"shadow:{role_key}", lane=lane)
                return role
            finally:
                self.comm.mode = prev_mode
                self.comm.sandbox_members = prev_members

    # ------------------------------------------------------- state moves
    def get_state(self, mid: int) -> dict:
        """A host snapshot of a machine's train state, as the in-memory
        checkpoint and storage keep it. Flat path: the flat optimizer
        state (`opt`: m, v, the fp32 master weights, Adam's step), the
        step and the stage, and no params: `set_state` rebuilds them
        from the master weights. Per-leaf path: params, opt and step
        as trees."""
        m = self.cluster[mid]
        with tracing.span("tm:get_state"):
            if self.use_flat_buffers:
                state = {"opt": m.payload["opt"], "step": m.payload["step"],
                         "stage": m.payload["_seg_stage"]}
            else:
                state = {k: m.payload[k] for k in ("params", "opt", "step")}
            state = jax.device_get(state)
            tracing.count("bytes.d2h", tree_bytes(state))
        return state

    def param_rebuild_fn(self, stage: int):
        """A stage's params in tree form from its flat master vector,
        by the update's own slice and cast (`params_from_master`), so
        bitwise the params the update made. Jitted once a stage,
        outside compile_role, which re-lowers every role program in
        each recovery."""
        if stage not in self._param_rebuild:
            spec = self.flat_spec(stage)

            def rebuild_params(master):
                return spec.unflatten(
                    opt_mod.params_from_master(spec, master))

            self._param_rebuild[stage] = jax.jit(rebuild_params)
        return self._param_rebuild[stage]

    def set_state(self, mid: int, state: dict) -> None:
        """Restore a `get_state` snapshot onto `mid`, which may be a
        joiner outside the grid. A flat snapshot's params are rebuilt
        on the device from its master weights, in tree form, as a
        per-leaf snapshot brings them: the machine's own buckets would
        not be its DP peer's, so its next forward pass would unflatten
        them into a second copy."""
        m = self.cluster[mid]
        with tracing.span("tm:set_state"):
            if "params" in state:
                m.payload.update(jax.tree.map(jnp.asarray, state))
            else:
                s = int(state["stage"])
                opt = jax.device_put(state["opt"])
                m.payload.update(
                    params=self.param_rebuild_fn(s)(opt["master"]),
                    param_segs=None, _seg_stage=s, opt=opt,
                    step=jnp.asarray(state["step"]))
                tracing.count("ckpt.params_rebuilt", 1)
            tracing.count("bytes.h2d", tree_bytes(state))
        # the real state supersedes the shadow iteration's warm-up
        # state (nothing reads it again); on one device every machine
        # shares its memory, so holding both would cost a stage copy
        m.payload.pop("sandbox_state", None)

    def opt_state_tree(self, d: int, s: int) -> dict:
        """Optimizer state in per-leaf tree form (flat vectors are
        unflattened through the stage spec) — parity tests and
        inspection tooling use this to compare paths."""
        opt = self.machine(d, s).payload["opt"]
        if not self.use_flat_buffers:
            return opt
        spec = self.flat_spec(s)
        return {k: spec.unflatten_master(opt[k])
                for k in ("m", "v", "master")} | {"step": opt["step"]}

    def state_spec(self, stage: int) -> flatbuf.ByteSpec:
        """Byte layout of a stage's full train state (params + opt),
        shared by every DP replica of that stage. On the flat path the
        layout is the already-flat buffers themselves — param segment
        buckets plus the flat optimizer vectors — so packing is a
        straight memcpy with no pytree walk."""
        if stage not in self._state_specs:
            pspec = self._stage_param_spec(stage)
            if self.use_flat_buffers:
                spec = self.flat_spec(stage)
                tree = {"param_segs": tuple(
                            jax.ShapeDtypeStruct((g.size,), g.dtype)
                            for g in spec.segments),
                        "opt": jax.eval_shape(
                            lambda p: opt_mod.init_flat_opt_state(spec, p),
                            pspec)}
            else:
                tree = {"params": pspec,
                        "opt": jax.eval_shape(opt_mod.init_opt_state,
                                              pspec)}
            self._state_specs[stage] = flatbuf.ByteSpec.from_tree(tree)
        return self._state_specs[stage]

    def get_state_flat(self, mid: int) -> Tuple[np.ndarray, int]:
        """(contiguous uint8 state buffer, step) — the §8.5 transfer
        unit: one buffer over the repurposed gradient channel. Flat
        path: a memcpy of the live 1-D buffers, params never
        unflattened on the leaver."""
        d, s = self.coords_of(mid)
        m = self.cluster[mid]
        with tracing.span("tm:get_state_flat"):
            if self.use_flat_buffers:
                segs = m.payload.get("param_segs")
                if segs is None:                # tree-form restore
                    segs = self.flat_spec(s).flatten(m.payload["params"])
                buf = self.state_spec(s).pack(
                    {"param_segs": tuple(segs), "opt": m.payload["opt"]})
            else:
                buf = self.state_spec(s).pack(
                    {"params": m.payload["params"],
                     "opt": m.payload["opt"]})
            tracing.count("bytes.d2h", buf.nbytes)
        return buf, int(m.payload["step"])

    def set_state_flat(self, mid: int, stage: int, buf: np.ndarray,
                       step: int) -> None:
        m = self.cluster[mid]
        with tracing.span("tm:set_state_flat"):
            tree = self.state_spec(stage).unpack(buf)
            if self.use_flat_buffers:
                m.payload["param_segs"] = tuple(
                    jnp.asarray(b) for b in tree["param_segs"])
                m.payload["params"] = None      # lazy: next fwd/bwd
                m.payload["_seg_stage"] = stage
            else:
                m.payload["params"] = jax.tree.map(jnp.asarray,
                                                   tree["params"])
            m.payload["opt"] = jax.tree.map(jnp.asarray, tree["opt"])
            tracing.count("bytes.h2d", buf.nbytes)
        m.payload["step"] = step
        m.payload.pop("sandbox_state", None)    # as in set_state

    def reshard_machine(self, mid: int) -> int:
        """Re-bucket a machine's state for an intra-machine re-shard:
        after a partial-GPU fault the survivors own bigger slices of
        the stage shard, so the flat param/optimizer buffers re-pack
        for the new device layout. The bytes are bitwise identical —
        only the layout moves — which is what keeps re-shard recovery
        loss-parity-exact by construction. Returns the bytes re-laid.

        The tape needs no re-record: shadow replay is keyed by role
        type, and the stage's role (and its recorded collectives) are
        unchanged by an intra-machine re-split."""
        _, s = self.coords_of(mid)
        buf, step = self.get_state_flat(mid)
        self.set_state_flat(mid, s, buf, step)
        return buf.nbytes

    def epoch_signature(self) -> Dict[int, int]:
        """Per-machine committed step counter across the training grid.
        A consistent epoch — the invariant migration rollback must
        restore — means every machine reports the same value."""
        return {mid: int(self.cluster[mid].payload["step"])
                for mid in self.grid.values()}

    def swap_machine(self, leaver: int, joiner: int) -> None:
        """Replace leaver with joiner in the grid + role bookkeeping."""
        d, s = self.coords_of(leaver)
        self.grid[(d, s)] = joiner
        self._coords.pop(leaver, None)
        self._coords[joiner] = (d, s)
        for k, h in list(self.hosted.items()):
            if h == leaver:                 # leaver was hosting: the
                self.hosted[k] = joiner     # joiner inherits the rank
        jm, lm = self.cluster[joiner], self.cluster[leaver]
        jm.role, lm.role = lm.role, None
        jm.status = NodeStatus.TRAINING
        if lm.status != NodeStatus.DEAD:
            lm.status = NodeStatus.IDLE

    def dp_retire(self, d_gone: int) -> List[int]:
        """Degraded-mode shrink: retire DP chain `d_gone` from the
        physical grid. Every (d_gone, s) logical rank is re-hosted by a
        surviving same-stage replica — no state moves, because DP
        replicas hold bitwise-identical stage state after every update;
        the host only allocates a second gradient bucket for the rank
        it now serves. The chain's still-alive machines are released to
        IDLE (they become the spares that absorb the rest of the storm)
        and returned."""
        assert 0 <= d_gone < self.dp, d_gone
        freed: List[int] = []
        for s in range(self.pp):
            host = None
            for d in range(self.dp):
                if d != d_gone and (d, s) in self.grid:
                    host = self.grid[(d, s)]
                    break
            assert host is not None, f"no surviving replica for stage {s}"
            mid = self.grid.pop((d_gone, s), None)
            self.hosted[(d_gone, s)] = host
            hm = self.cluster[host]
            hm.device.alloc(self.grad_buffer_bytes(s),
                            f"hosted_grad:d{d_gone}", self.clock.now)
            if mid is not None:
                self._coords.pop(mid, None)
                m = self.cluster[mid]
                # ranks the retiring machine was itself hosting move to
                # the new host with it, bucket and all
                for k, h in list(self.hosted.items()):
                    if h == mid and k != (d_gone, s):
                        self.hosted[k] = host
                        hm.device.alloc(self.grad_buffer_bytes(s),
                                        f"hosted_grad:d{k[0]}",
                                        self.clock.now)
                        m.device.free(f"hosted_grad:d{k[0]}",
                                      self.clock.now)
                m.role = None
                if m.status != NodeStatus.DEAD:
                    m.status = NodeStatus.IDLE
                    m.device.free("grad_buffer", self.clock.now)
                    # stale the moment training resumes without it; a
                    # later re-use as a joiner re-allocs the tag fresh
                    m.device.free("train_state", self.clock.now)
                    freed.append(mid)
        return freed

    def dp_restaff(self, d: int, stage_mids: Dict[int, int]) -> None:
        """Re-grow a retired DP chain: staff `d` with one machine per
        stage, clearing the hosted overlay and the hosts' extra
        gradient buckets. Callers ship each new machine a bitwise copy
        of its DP peer's state (state_sync.regrow_staff) before
        training resumes, so parity with the uninterrupted reference
        holds by construction."""
        for s in range(self.pp):
            host = self.hosted.pop((d, s))
            self.cluster[host].device.free(f"hosted_grad:d{d}",
                                           self.clock.now)
            mid = stage_mids[s]
            self.grid[(d, s)] = mid
            self._coords[mid] = (d, s)
            m = self.cluster[mid]
            m.status = NodeStatus.TRAINING
            m.role = Role(d, s, self.pp)

    def state_bytes(self, mid: int) -> int:
        payload = self.cluster[mid].payload
        params = payload["params"]
        if params is None:                      # still in bucket form
            params = payload["param_segs"]
        return tree_bytes({"params": params, "opt": payload["opt"]})
