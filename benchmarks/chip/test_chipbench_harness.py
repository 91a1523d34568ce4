"""CPU checks of the chip benchmark's harness: the trace reduction, the
operation and byte counts, the data it finds by name, and its refusal to
run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from chipbench import flops, spec, trace_reduce  # noqa: E402

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


# ---------------------------------------------------------- trace reduce
def _trace():
    """Window 0..100 ns: ops at 10-30, 20-40 (overlapping), 60-70;
    programs fwd 10-40, upd 60-70 and a fwd outside the window; host
    spans train 6-50 with ckpt_put 35-55 inside it, failure 55-95."""
    return trace_reduce.Trace(
        ops={"/device:TPU:0": [(10, 30), (20, 40), (60, 70), (120, 130)]},
        programs=[("jit_fwd", 10, 40), ("jit_upd_flat", 60, 70),
                  ("jit_fwd", 120, 130)],
        spans=[("train", 6, 50), ("ckpt_put", 35, 55),
               ("failure", 55, 95)],
        window=(0, 100), devices=["/device:TPU:0"])


def test_busy_union_and_window():
    t = _trace()
    assert trace_reduce.busy_s(t) == pytest.approx(40e-9)
    assert trace_reduce.window_s(t) == pytest.approx(100e-9)


def test_program_time_inside_window_and_spans():
    t = _trace()
    assert trace_reduce.program_s(t, "jit_fwd") == (pytest.approx(30e-9), 1)
    assert trace_reduce.program_s(t, r"jit_(fwd|upd_flat)",
                                  within=("train",)) == (
        pytest.approx(30e-9), 1)
    assert trace_reduce.top_programs(t) == [
        ["jit_fwd", pytest.approx(30e-9)],
        ["jit_upd_flat", pytest.approx(10e-9)]]


def test_idle_gaps_labelled_by_innermost_span():
    # gaps 70-100 (middle 85: failure), 40-60 (middle 50: train and
    # ckpt_put, the later-starting wins) and 0-10 (middle 5: no span)
    assert trace_reduce.idle_gaps(_trace()) == [
        ["failure", pytest.approx(30e-9)],
        ["ckpt_put", pytest.approx(20e-9)],
        ["none", pytest.approx(10e-9)]]


def test_program_name_strips_execution_id():
    assert trace_reduce.program_name("jit_mid_bwd(1234)") == "jit_mid_bwd"
    assert trace_reduce.program_name("jit__lambda_") == "jit__lambda_"


# ----------------------------------------------------------- flops/bytes
def test_flops_per_token_by_hand():
    med = spec.config("gpt-medium")
    # 4 layers x (4 x 1024^2 + 3 x 1024 x 4096) + 1024 x 50304 weights of
    # matmuls, 6 FLOPs each; attention 12 x 4 x 16 x 64 x 2048
    n = 4 * (4 * 1024 ** 2 + 3 * 1024 * 4096) + 1024 * 50304
    assert flops.model_flops_per_token(med) == 6 * n + 12 * 4 * 1024 * 2048
    assert flops.model_flops_per_token(med) == 812_384_256
    big = spec.config("gpt-2.7b")
    n = 2 * (4 * 2560 ** 2 + 3 * 2560 * 10240) + 2560 * 6288
    assert flops.model_flops_per_token(big) == 6 * n + 12 * 2 * 2560 * 2048
    assert flops.model_flops_per_token(big) == 1_480_704_000


def test_update_bytes_by_hand():
    med = spec.config("gpt-medium")
    layer = 4 * 1024 ** 2 + 3 * 1024 * 4096 + 2 * 1024
    # float32 everywhere: 4 + 4 + 6 x 4 bytes per element
    assert flops.update_bytes(med, 0) == (2 * layer + 50304 * 1024) * 32
    assert flops.update_bytes(med, 1) == \
        (2 * layer + 1024 + 1024 * 50304) * 32
    big = spec.config("gpt-2.7b")
    layer = 4 * 2560 ** 2 + 3 * 2560 * 10240 + 2 * 2560
    # block weights bfloat16 (2 + 2 + 24), the rest float32 (32)
    assert flops.update_bytes(big, 0) == layer * 28 + 6288 * 2560 * 32
    assert flops.update_bytes(big, 1) == \
        layer * 28 + (2560 + 2560 * 6288) * 32
    assert flops.tokens_per_iteration(big) == 4 * 2048


# -------------------------------------------------------- data by name
def test_every_metric_resolves_to_a_reader():
    for m in spec.metrics(BENCH):
        assert callable(spec.reader(m.name)), m.name


def test_cells_configs_and_traffic_resolve():
    names = [w["name"] for w in BENCH["workloads"]]
    assert len(set(names)) == len(names)
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        cell = spec.cell(w["name"], BENCH)
        assert cell.config["name"] == w["config"] in configs
        assert set(cell.traffic["warmup"]) | set(cell.traffic["window"]) \
            <= {"train", "event"}
        e2e = [m.name for m in cell.metrics if m.kind == "end_to_end"]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(m.kind == "per_layer" for m in cell.metrics)
    for c in BENCH["configs"]:
        cfg = spec.config(c["name"])
        assert (HERE.parents[1] / c["file"]).is_file()
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key, (published, run) in cfg["reduced"].items():
            held = cfg[key]
            if isinstance(run, dict):       # a group: the keys changed
                held = {k: held[k] for k in run}
            assert held == run != published
        for key, value in cfg["published"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key


def test_names_and_per_layer_entries():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[kind]:
            assert NAME.match(e["name"]), e["name"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert 0 < len(m["layer"]) <= 200


def test_peaks_unknown_device_is_an_error():
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("cpu")


# -------------------------------------------------------- no chip, no run
def _run(cwd, env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "gpt-medium.steady", "--seed", "3", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def _no_result(out):
    last = (out.stdout.strip().splitlines() or [""])[-1]
    assert out.returncode != 0
    assert not last.startswith("{"), last


def test_refuses_to_run_on_the_cpu():
    out = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    _no_result(out)
    assert "needs 1 TPU chip" in out.stderr


def test_refuses_to_run_from_the_benchmark_files_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(".jax_cache", ".out",
                                                      "__pycache__"))
    _no_result(_run(tmp_path, {"JAX_PLATFORMS": "cpu"}))


def test_recorded_tpu_trace(tmp_path):
    """A trace recorded on a TPU v5e: two jitted programs (both named
    `jit__lambda`) run three times, the second inside `ckpt_put` within
    `train`, with a 2 ms sleep after each `train`. Host launches and
    completions bound the device clock's offset to 1.298-1.787 ms."""
    shutil.copy(HERE / "testdata" / "tiny_tpu.xplane.pb", tmp_path)
    t = trace_reduce.load(str(tmp_path), ("train", "ckpt_put"))
    assert t.devices == ["/device:TPU:0"]
    assert 1_298_253 <= t.offset_ns <= 1_786_978
    assert [n for n, _, _ in t.spans].count("train") == 3
    total, runs = trace_reduce.program_s(t, "jit__lambda")
    assert runs == 6
    assert trace_reduce.program_s(t, "jit__lambda", within=("train",))[1] \
        == 6
    assert trace_reduce.program_s(t, "jit__lambda",
                                  within=("ckpt_put",))[1] == 3
    assert 0 < trace_reduce.busy_s(t) <= total
    gaps = trace_reduce.idle_gaps(t)
    assert gaps[0][0] == "none" and gaps[0][1] > 2e-3
