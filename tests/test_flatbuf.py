"""Flat-buffer gradient bucketing: layout round-trips (including the
per-dtype SegmentedSpec), bitwise parity of the bucketed fully-flat
hot path against the per-leaf reference path — in fp32 and in mixed
bf16/fp32 — and the RECORD -> REPLAY round-trip through the fused tape
keys."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.cluster.node import Cluster
from repro.cluster.simclock import SimClock
from repro.configs.gpt import tiny_gpt
from repro.core import flatbuf
from repro.core.engine import PipelineEngine
from repro.core.sandbox import CommHooks
from repro.train import optimizer as opt_mod

CFG = tiny_gpt(layers=4, d=64, heads=4, vocab=256)


def build_engine(flat: bool, machines: int = 8,
                 param_dtype=jnp.float32) -> PipelineEngine:
    cluster = Cluster(machines, device_capacity=16 * 2 ** 30)
    clock = SimClock()
    comm = CommHooks(clock)
    eng = PipelineEngine(CFG, dp=2, pp=2, global_batch=8, seq_len=32,
                         cluster=cluster, clock=clock, comm=comm,
                         micro_batches=2, use_flat_buffers=flat,
                         param_dtype=param_dtype)
    eng.setup(list(range(4)))
    return eng


def assert_trees_equal(a, b, check_dtype: bool = False):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        if check_dtype:
            assert np.asarray(x).dtype == np.asarray(y).dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------------ layouts
def test_flatspec_roundtrip():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.float32),
                  "d": jnp.zeros((1, 2, 2), jnp.float32)}}
    spec = flatbuf.FlatSpec.from_tree(tree)
    assert spec.size == 6 + 4 + 4
    buf = spec.flatten(tree)
    assert buf.shape == (spec.size,)
    back = spec.unflatten(buf)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flatspec_rejects_mixed_dtypes():
    with pytest.raises(TypeError):
        flatbuf.FlatSpec.from_tree({"a": jnp.ones(2, jnp.float32),
                                    "b": jnp.ones(2, jnp.int32)})


def test_segmented_spec_mixed_dtypes_roundtrip():
    """bf16 grads and fp32 reductions both bucket: one contiguous
    segment per dtype, exact round-trip."""
    tree = {"w": jnp.ones((3, 4), jnp.bfloat16),
            "ln": jnp.linspace(0, 1, 8).astype(jnp.float32),
            "b": {"m": jnp.full((2, 2), 2.0, jnp.bfloat16)}}
    spec = flatbuf.SegmentedSpec.from_tree(tree)
    assert len(spec.segments) == 2
    assert spec.size == 12 + 8 + 4
    assert spec.nbytes == (12 + 4) * 2 + 8 * 4
    bufs = spec.flatten(tree)
    assert [b.dtype for b in bufs] == [s.dtype for s in spec.segments]
    assert all(b.ndim == 1 for b in bufs)
    assert_trees_equal(tree, spec.unflatten(bufs), check_dtype=True)


def test_segmented_spec_single_dtype_degenerates_to_flat():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": jnp.ones((4,), jnp.float32)}
    seg = flatbuf.SegmentedSpec.from_tree(tree)
    ref = flatbuf.FlatSpec.from_tree(tree)
    assert len(seg.segments) == 1
    assert seg.size == ref.size and seg.nbytes == ref.nbytes
    np.testing.assert_array_equal(np.asarray(seg.flatten(tree)[0]),
                                  np.asarray(ref.flatten(tree)))


def test_segmented_spec_master_space():
    """Flat optimizer vectors live in the segment-major master space;
    unflatten_master must invert the leaf placement exactly."""
    tree = {"w": jnp.zeros((2, 3), jnp.bfloat16),
            "ln": jnp.zeros((4,), jnp.float32),
            "v": jnp.zeros((5,), jnp.bfloat16)}
    spec = flatbuf.SegmentedSpec.from_tree(tree)
    bounds = spec.segment_bounds()
    assert bounds[0][0] == 0 and bounds[-1][1] == spec.size
    vec = jnp.arange(spec.size, dtype=jnp.float32)
    back = spec.unflatten_master(vec)
    # each leaf's values are the contiguous run at its segment offset
    for (si, off, n, sh), leaf in zip(spec.leaf_views(),
                                      jax.tree.leaves(back)):
        lo = bounds[si][0] + off
        np.testing.assert_array_equal(
            np.asarray(leaf).reshape(-1), np.arange(lo, lo + n))


_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)


@st.composite
def _leaf_specs(draw):
    n_leaves = draw(st.integers(1, 6))
    return [(draw(st.sampled_from(_DTYPES)),
             tuple(draw(st.lists(st.integers(1, 4), min_size=0,
                                 max_size=3))))
            for _ in range(n_leaves)]


@settings(max_examples=30, deadline=None)
@given(_leaf_specs())
def test_segmented_spec_property_roundtrip(leaf_specs):
    """Property: flatten/unflatten round-trips any mixed-dtype tree,
    sizes add up, and master-space bounds tile [0, size)."""
    tree = {f"leaf{i}": (jnp.arange(int(np.prod(sh, dtype=np.int64)),
                                    dtype=jnp.float32)
                         .reshape(sh).astype(dt))
            for i, (dt, sh) in enumerate(leaf_specs)}
    spec = flatbuf.SegmentedSpec.from_tree(tree)
    assert spec.size == sum(int(np.prod(sh, dtype=np.int64))
                            for _, sh in leaf_specs)
    assert len({s.dtype for s in spec.segments}) == len(spec.segments)
    bufs = spec.flatten(tree)
    assert sum(b.size for b in bufs) == spec.size
    assert_trees_equal(tree, spec.unflatten(bufs), check_dtype=True)
    bounds = spec.segment_bounds()
    assert [hi - lo for lo, hi in bounds] == [s.size
                                              for s in spec.segments]


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 5), st.sampled_from((jnp.float32, jnp.bfloat16)))
def test_flat_adam_matches_per_leaf_adam(n_leaves, dtype):
    """Property: adam_update_flat on segment buckets is bitwise
    identical to adam_update on the unflattened tree, mixed dtypes
    included (fp32 'ln' leaf alongside `dtype` leaves)."""
    cfg = opt_mod.AdamCfg(lr=1e-3, warmup_steps=10)
    key = jax.random.PRNGKey(0)
    tree = {}
    for i in range(n_leaves):
        key, k1 = jax.random.split(key)
        tree[f"w{i}"] = jax.random.normal(k1, (3, i + 2)).astype(dtype)
    tree["ln"] = jnp.linspace(-1, 1, 7).astype(jnp.float32)
    spec = flatbuf.SegmentedSpec.from_tree(tree)
    leaves, tdef = jax.tree.flatten(tree)
    gkeys = jax.random.split(key, len(leaves))
    grads = tdef.unflatten(
        [jax.random.normal(k, p.shape).astype(p.dtype)
         for k, p in zip(gkeys, leaves)])
    opt_tree = opt_mod.init_opt_state(tree)
    opt_flat = opt_mod.init_flat_opt_state(spec, tree)
    p_ref, o_ref, s_ref = opt_mod.adam_update(grads, opt_tree, cfg,
                                              param_dtype=None)
    segs, o_flat, s_flat = opt_mod.adam_update_flat(
        spec, spec.flatten(grads), opt_flat, cfg)
    np.testing.assert_array_equal(np.asarray(s_ref["grad_norm"]),
                                  np.asarray(s_flat["grad_norm"]))
    assert_trees_equal(p_ref, spec.unflatten(segs), check_dtype=True)
    for k in ("m", "v", "master"):
        assert_trees_equal(o_ref[k], spec.unflatten_master(o_flat[k]))


def test_bytespec_roundtrip_mixed_dtypes():
    tree = {"w": np.linspace(0, 1, 12, dtype=np.float32).reshape(3, 4),
            "n": np.arange(5, dtype=np.int32),
            "s": np.int64(7)}
    spec = flatbuf.ByteSpec.from_tree(tree)
    buf = spec.pack(tree)
    assert buf.dtype == np.uint8 and buf.nbytes == spec.nbytes
    back = spec.unpack(buf)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bytespec_built_from_shape_structs():
    """Joiners unpack buffers for roles they never held: the spec must
    be derivable from eval_shape metadata alone."""
    tree = {"w": np.ones((3, 4), np.float32)}
    spec_meta = flatbuf.ByteSpec.from_tree(
        {"w": jax.ShapeDtypeStruct((3, 4), jnp.float32)})
    buf = spec_meta.pack(tree)
    np.testing.assert_array_equal(spec_meta.unpack(buf)["w"], tree["w"])


# ----------------------------------------------------- engine numerics
# these build real engines (XLA compiles); the layout tests above stay
# in the fast -m "not slow" loop
engine_test = pytest.mark.slow


@pytest.fixture(scope="module")
def engines():
    flat, ref = build_engine(True), build_engine(False)
    return flat, ref


@engine_test
def test_bucketed_path_matches_per_leaf_bitwise(engines):
    """Flat-bucket all-reduce + fully-flat Adam + single-update-
    broadcast must reproduce the per-leaf reference losses, params and
    optimizer state exactly over >=3 iters."""
    flat, ref = engines
    losses_flat = [flat.train_iteration() for _ in range(3)]
    losses_ref = [ref.train_iteration() for _ in range(3)]
    assert losses_flat == losses_ref, "losses must be bitwise identical"
    for d in range(2):
        for s in range(2):
            assert_trees_equal(flat._stage_params(flat.machine(d, s)),
                               ref.machine(d, s).payload["params"],
                               check_dtype=True)
            assert_trees_equal(flat.opt_state_tree(d, s),
                               ref.opt_state_tree(d, s))


@engine_test
def test_mixed_precision_segmented_parity():
    """bf16 stack grads + fp32 norm/embed grads bucket into per-dtype
    segments; the segmented fully-flat path stays bitwise identical to
    the per-leaf reference in mixed precision too."""
    flat = build_engine(True, param_dtype=jnp.bfloat16)
    ref = build_engine(False, param_dtype=jnp.bfloat16)
    assert len(flat.flat_spec(0).segments) == 2     # embed f32 + stack
    losses_flat = [flat.train_iteration() for _ in range(3)]
    losses_ref = [ref.train_iteration() for _ in range(3)]
    assert losses_flat == losses_ref
    # one collective per dtype segment per stage, still O(1) per stage
    assert flat.comm.op_counts["all_reduce"] == \
        sum(len(flat.flat_spec(s).segments) for s in range(flat.pp))
    for d in range(2):
        for s in range(2):
            assert_trees_equal(flat._stage_params(flat.machine(d, s)),
                               ref.machine(d, s).payload["params"],
                               check_dtype=True)
            assert_trees_equal(flat.opt_state_tree(d, s),
                               ref.opt_state_tree(d, s))


@engine_test
def test_bucketing_fuses_the_collective(engines):
    """>=2x fewer all_reduce hook invocations per iteration (one per
    stage bucket instead of one per leaf)."""
    flat, ref = engines
    flat.train_iteration()
    ref.train_iteration()
    n_flat = flat.comm.op_counts["all_reduce"]
    n_ref = ref.comm.op_counts["all_reduce"]
    assert n_flat == flat.pp            # exactly one bucket per stage
    assert n_ref >= 2 * n_flat, (n_ref, n_flat)


@engine_test
def test_record_replay_roundtrip_with_fused_keys():
    """RECORD writes one bucket entry per stage; a joiner's shadow
    iteration replays it from the tape (fewer entries than the per-leaf
    tape, same replayed bytes semantics)."""
    eng = build_engine(True)
    eng.record_iteration()
    tape = eng.comm.tape
    ar_keys = [k for k in tape.entries
               if k[1] == "all_reduce" and isinstance(k[0], int)]
    assert all(k[2] == "gradbucket" for k in ar_keys)
    assert len(ar_keys) == eng.pp       # one fused entry per stage
    for k in ar_keys:                   # each bucket = its stage's spec
        assert tape.get(k).shape == (eng.flat_spec(k[0]).size,)

    ref = build_engine(False)
    ref.record_iteration()
    ref_ar = [k for k in ref.comm.tape.entries
              if k[1] == "all_reduce" and isinstance(k[0], int)]
    assert len(ref_ar) >= 2 * len(ar_keys), "tape must shrink"

    # joiner replay through the fused keys
    jm = eng.cluster[6]
    eng.comm.replay_bytes = 0
    role = eng.shadow_iteration(jm, 1, 1)
    assert eng.comm.replay_bytes >= eng.flat_spec(1).nbytes
    assert 1 in jm.warm_roles and role.compile_seconds > 0


@engine_test
def test_flat_state_transfer_is_exact():
    """leaver->joiner ships one contiguous buffer, bit-for-bit."""
    eng = build_engine(True)
    eng.train_iteration()
    src = eng.grid[(1, 1)]
    buf, step = eng.get_state_flat(src)
    assert buf.dtype == np.uint8
    ref_state = eng.get_state(src)
    eng.set_state_flat(7, 1, buf, step)
    got = eng.get_state(7)
    assert got["step"] == ref_state["step"]
    for a, b in zip(jax.tree.leaves(ref_state["params"]),
                    jax.tree.leaves(got["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(ref_state["opt"]),
                    jax.tree.leaves(got["opt"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
