"""Compile chip_smoke.py's stage programs for a described TPU v5e chip.

The one test file that describes a chip: it lowers and compiles, for
`devices[0]` of a `v5e:2x2` topology, the smoke's stage-0 `mid_bwd`,
last-stage `last_bwd` and flat `update` programs at the exact width,
depth and batch chip_smoke.py trains, and checks that each fits one
chip's 16 GiB. Nothing runs: this is what the chip's compiler would
refuse, caught without the chip.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402
from repro.core.engine import make_flat_update, make_stage_fns  # noqa: E402
from repro.train import optimizer as opt_mod  # noqa: E402

HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # keep the TPU compiler's logs out of the temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def engine():
    return chip_smoke.make_engine(chip_smoke.smoke_config(),
                                  chip_smoke.SEQ_LEN)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _program(engine, name):
    """(function, argument specs) of one smoke program, as the engine
    compiles it."""
    cfg, last = engine.cfg, engine.pp - 1
    tok = jax.ShapeDtypeStruct((engine.mb_size, engine.seq_len), jnp.int32)
    act = jax.ShapeDtypeStruct((engine.mb_size, engine.seq_len,
                                cfg.d_model), jnp.float32)
    if name == "mid_bwd":
        return (make_stage_fns(cfg, 0, engine.pp)["mid_bwd"],
                (engine._stage_param_spec(0), tok, act))
    if name == "last_bwd":
        return (make_stage_fns(cfg, last, engine.pp)["last_bwd"],
                (engine._stage_param_spec(last), act, tok))
    spec = engine.flat_spec(0)
    segs = tuple(jax.ShapeDtypeStruct((g.size,), g.dtype)
                 for g in spec.segments)
    opt = jax.eval_shape(lambda p: opt_mod.init_flat_opt_state(spec, p),
                         engine._stage_param_spec(0))
    return (make_flat_update(spec, engine.adam),
            (segs, opt, jax.ShapeDtypeStruct((), jnp.float32)))


@pytest.mark.parametrize("name", ["mid_bwd", "last_bwd", "update"])
def test_smoke_program_compiles_for_one_v5e_chip(engine, one_chip, name):
    assert engine.cfg.d_model == 1024 and engine.cfg.vocab_size == 50304
    fn, args = _program(engine, name)
    compiled = jax.jit(fn).lower(*_on(one_chip, args)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < HBM_BYTES, (name, total)
