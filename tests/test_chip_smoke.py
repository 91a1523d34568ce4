"""chip_smoke.py rehearsed on the CPU: its phase functions at a tiny
width (2 layers, d=128, as `quickstart --small`), its refusal to run
without a TPU, and the compile-cache helper every entry point calls."""
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, _REPO)
import chip_smoke  # noqa: E402
from repro.configs.gpt import tiny_gpt  # noqa: E402

TINY = tiny_gpt(layers=2, d=128, heads=4, vocab=512)
SEQ = 64


def _run(args, env_extra, cwd=_REPO):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_device_check_refuses_cpu():
    assert os.environ.get("JAX_PLATFORMS") == "cpu"
    with pytest.raises(RuntimeError, match="no TPU"):
        chip_smoke.device_check()


def test_phases_bitwise_parity_and_release():
    before = chip_smoke.live_bytes()
    ref = chip_smoke.reference_run(TINY, SEQ)
    # the released reference controller leaves nothing on the device
    assert ref["live_bytes_after"] <= before
    got = chip_smoke.interrupted_run(TINY, SEQ)
    assert len(ref["losses"]) == len(got["losses"]) == chip_smoke.ITERS
    par = chip_smoke.parity(ref["losses"], got["losses"])
    assert par == {"identical": True, "max_abs_diff": 0.0,
                   "first_divergence": None, "falling": True}
    assert got["migration_downtime"] > 0 and got["failure_downtime"] > 0
    assert set(got["compile_s"]) >= {"stage0", "stage1"}
    assert len(ref["walls"]) == len(got["walls"]) == chip_smoke.ITERS


def test_parity_reports_first_divergence():
    par = chip_smoke.parity([3.0, 2.0, 1.0], [3.0, 2.0, 1.5])
    assert not par["identical"]
    assert par["first_divergence"] == 2 and par["max_abs_diff"] == 0.5
    assert not chip_smoke.parity([3.0, 2.0], [3.0, 2.0, 1.0])["identical"]


def test_script_fails_without_tpu(tmp_path):
    cache = tmp_path / "cache"
    out = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu",
                                   "JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU attached" in out.stderr
    assert not cache.exists() or not any(cache.iterdir())


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    out = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"}, cwd=tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(tmp_path, env_dir):
    """The helper leaves a set JAX_COMPILATION_CACHE_DIR to JAX and
    otherwise points the cache at the checkout's fixed .jax_cache.
    Checked in a child that compiles nothing, so no entry is written."""
    env = {"JAX_PLATFORMS": "cpu"}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    out = _run(["-c", "import jax; from repro.launch import compile_cache;"
                " print(compile_cache.enable());"
                " print(jax.config.jax_compilation_cache_dir)"],
               {**env, "PYTHONPATH": os.path.join(_REPO, "src")})
    assert out.returncode == 0, out.stderr
    used, configured = out.stdout.split()
    want = (str(tmp_path / env_dir) if env_dir else
            os.path.realpath(os.path.join(_REPO, ".jax_cache")))
    assert used == configured == want
