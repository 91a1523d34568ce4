"""CPU checks that a configuration brings its architecture as a model
module found by name: the GPT block, moved into `models/gpt_block.py`,
gives the readings and counts it gave before the move, bit for bit; a
toy architecture (`testdata/models/toy_mlp.py`) runs the reference, the
comparison and the counts with no other harness file changed; and an
unknown model is an error."""
import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import calibrate  # noqa: E402
from chipbench import compare, flops, reference, spec  # noqa: E402

PINS = json.loads((HERE / "testdata" / "gpt_block_pins.json").read_text())


def _sha(leaves: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(leaves):
        h.update(k.encode())
        h.update(np.asarray(leaves[k], np.float32).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config", ["gpt-medium", "gpt-2.7b"])
@pytest.mark.parametrize("precision", ["reference", "control"])
def test_gpt_block_follow_is_pinned(config, precision):
    cfg = {**spec.config(config), **PINS["tiny"]}
    got = reference.follow(cfg, PINS["seed"], PINS["steps"], precision)
    pin = PINS[f"{config}/{precision}"]
    assert [x.hex() for x in got["losses"]] == pin["losses"]
    assert {f"{s}/{n}": v.hex() for (s, n), v in
            got["grad_norms"].items()} == pin["grad_norms"]
    assert _sha(got["master"]) == pin["master_sha256"]
    assert _sha(got["grads0"]) == pin["grads0_sha256"]


@pytest.mark.parametrize("config", ["gpt-medium", "gpt-2.7b"])
@pytest.mark.parametrize("size", ["full", "tiny"])
def test_gpt_block_counts_are_pinned(config, size):
    cfg = spec.config(config)
    if size == "tiny":
        cfg = {**cfg, **PINS["tiny"]}
    pin = PINS[f"{config}/{size}/counts"]
    model = spec.model(cfg)
    assert model.model_flops_per_token(cfg) == pin["model_flops_per_token"]
    assert flops.iteration_update_bytes(cfg) == pin["iteration_update_bytes"]
    assert [model.stage_elements(cfg, s) for s in range(cfg["pp"])] == \
        pin["stage_elements"]


def test_config_without_model_key_is_the_gpt_block():
    for name in ("gpt-medium", "gpt-2.7b"):
        cfg = spec.config(name)
        assert "model" not in cfg
        assert spec.model(cfg) is spec.model({"model": "gpt_block"})
    arch = spec.model(spec.config("gpt-2.7b")).arch(spec.config("gpt-2.7b"))
    assert (arch.family, arch.d_model, arch.kq_dim) == ("dense", 2560, 80)


def test_unknown_model_is_an_error():
    with pytest.raises(KeyError, match="no_such_model"):
        spec.model({"model": "no_such_model"})


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setattr(spec, "MODELS", HERE / "testdata" / "models")
    return spec.load_json(HERE / "testdata" / "configs" / "toy.json")


def test_new_architecture_is_one_module(toy):
    """The toy's reference follows its steps and its stage slices cover
    each leaf's layers once; the comparison reads the reference as
    sound, and the control and the planted faults apart from it."""
    mod = spec.model(toy)
    assert mod.__name__ == "chipbench_model_toy_mlp"
    slices = reference.stage_slices(toy)
    assert [(s, n) for s, n, _ in slices if n == "w_up"] == \
        [(0, "w_up"), (1, "w_up")]
    ref = reference.follow(toy, 5, 3)
    assert len(ref["losses"]) == 3 and ref["master"]["w_up"].shape == \
        (2, 16, 32)
    same = compare.gaps(_as_run(ref, toy),
                        compare.follow_readings(ref, toy))
    assert all(v == 0.0 for v in same.values()), same
    apart = calibrate.stand_ins(toy, 5, 3)
    for name in ("half_batch", "no_exchange"):
        assert apart[name]["update_gap"] > 0.05, (name, apart[name])
    assert apart["control"]["grad_err"] > 0.0


def _as_run(follow: dict, cfg: dict) -> dict:
    """The reference's readings in the program's form: lists over one
    replica."""
    want = compare.follow_readings(follow, cfg)
    return {"losses": want["losses"],
            **{k: {leaf: [x] for leaf, x in want[k].items()}
               for k in ("grad_norms", "grads", "update_norms")}}


def test_new_architecture_counts(toy):
    d, f, v = 16, 32, 64
    assert spec.model(toy).model_flops_per_token(toy) == \
        6.0 * (2 * 2 * d * f + d * v)
    # one layer per stage in bfloat16 (2 + 2 + 24 bytes an element), the
    # embedding, final norm and head in float32 (32 bytes)
    assert flops.update_bytes(toy, 0) == (2 * d * f + d) * 28 + v * d * 32
    assert flops.iteration_update_bytes(toy) == 2 * (2 * d * f + d) * 28 \
        + (v * d + d + d * v) * 32
