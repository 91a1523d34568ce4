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


def test_clock_offsets_cut_where_the_clocks_jump():
    """Runs 1-3 at a true offset of 5 ns, run 4 after a jump to 50 ns;
    run 2 has no callback and run 3 no enqueue. Each execution is
    enqueued 1-2 ns before it starts and called back 1-3 ns after it
    ends, on the host's clock."""
    runs = {1: (0, 10), 2: (100, 110), 3: (200, 210), 4: (300, 310)}
    enqueued = {1: 0 + 5 - 1, 2: 100 + 5 - 2, 4: 300 + 50 - 1}
    completed = {1: 10 + 5 + 3, 3: 210 + 5 + 1, 4: 310 + 50 + 2}
    offsets = trace_reduce.clock_offsets(runs, enqueued, completed)
    assert offsets == [(0, (4 + 6) / 2), (300, (49 + 52) / 2)]
    assert trace_reduce._offset(offsets, -20) == 5
    assert trace_reduce._offset(offsets, 299) == 5
    assert trace_reduce._offset(offsets, 300) == 50.5
    assert trace_reduce.clock_offsets({}, {}, {}) == []


def test_busy_within_spans_of_one_name():
    t = _trace()
    t.spans += [("tm:train_iteration", 5, 25), ("tm:train_iteration", 55, 65),
                ("tm:train_iteration", 90, 110)]      # the last outside
    busy, length = trace_reduce.busy_within(t, "tm:train_iteration")
    assert (busy, length) == (pytest.approx(20e-9), pytest.approx(30e-9))
    assert trace_reduce.busy_within(t, "tm:nothing") == (0.0, 0.0)


def test_program_name_strips_execution_id():
    assert trace_reduce.program_name("jit_mid_bwd(1234)") == "jit_mid_bwd"
    assert trace_reduce.program_name("jit__lambda_") == "jit__lambda_"


# ----------------------------------------------------------- flops/bytes
def test_flops_per_token_by_hand():
    med = spec.config("gpt-medium")
    per_token = spec.model(med).model_flops_per_token
    # 4 layers x (4 x 1024^2 + 3 x 1024 x 4096) + 1024 x 50304 weights of
    # matmuls, 6 FLOPs each; attention 12 x 4 x 16 x 64 x 2048
    n = 4 * (4 * 1024 ** 2 + 3 * 1024 * 4096) + 1024 * 50304
    assert per_token(med) == 6 * n + 12 * 4 * 1024 * 2048
    assert per_token(med) == 812_384_256
    big = spec.config("gpt-2.7b")
    n = 2 * (4 * 2560 ** 2 + 3 * 2560 * 10240) + 2560 * 6288
    assert per_token(big) == 6 * n + 12 * 2 * 2560 * 2048
    assert per_token(big) == 1_480_704_000


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


# ------------------------------------------------------- program spans
def _program_run():
    """A window of 5-105 s holding a migration (a warm-up with a role
    compile, a compile nested in it, a hand-off) and a failure (a
    promotion with a compile, a restore), a compile outside any
    recovery, and one checkpoint tick of four `get_state` calls of 1 GB
    each, a stage's DP replicas sharing one state (the second call of
    each finds the host copy); and, before the window, a recovery and a
    counter."""
    from chipbench import job, main
    from repro.core.tracing import Count, Span
    spans = [Span("tm:recovery", 1, 2, -1, {}),                   # 0
             Span("tm:recovery", 10, 20, -1, {"kind": "migration"}),
             Span("tm:step:warmup", 10, 12, 1, {}),
             Span("tm:compile_role", 10.5, 11.5, 2, {}),
             Span("tm:compile_role", 10.6, 11.0, 3, {}),
             Span("tm:step:xfer", 12, 16, 1, {}),                 # 5
             Span("tm:recovery", 30, 32, -1, {"kind": "failure"}),
             Span("tm:step:promote", 30, 31.5, 6, {}),
             Span("tm:compile_role", 30.2, 31.0, 7, {}),
             Span("tm:step:recover", 31.5, 31.625, 6, {}),
             Span("tm:compile_role", 40, 41, -1, {}),             # 10
             Span("tm:ckpt", 50, 52, -1, {}),
             Span("tm:get_state", 50, 50.5, 11, {}),
             Span("tm:get_state", 50.5, 50.55, 11, {}),
             Span("tm:get_state", 50.55, 51.05, 11, {}),           # 14
             Span("tm:get_state", 51.05, 51.1, 11, {}),
             Span("tm:imc_put", 51.1, 51.25, 11, {})]
    counts = [Count("bytes.d2h", 1.5, 5e9), Count("bytes.h2d", 50.5, 7)] \
        + [Count("bytes.d2h", sp.end, 1e9) for sp in spans[12:16]]
    run = main.Run(spec.cell("gpt-medium.churn", BENCH), 1.0, 100.0, 0,
                   job.Spans(), 5.0, 0, "TPU v5 lite")
    run.program_spans, run.program_counts = spans, counts
    return run


def test_program_spans_in_the_window_counted_once():
    run = _program_run()
    assert [sp.start for sp in run.program(("tm:recovery",))] == [10, 30]
    assert run.program_s(("tm:compile_role",)) == pytest.approx(2.8)
    assert run.program_s(("tm:compile_role",), inside="tm:recovery") == \
        pytest.approx(1.8)
    assert len(run.program(("tm:get_state",), inside="tm:ckpt")) == 4


def test_runtime_tracer_only_in_traced_runs():
    from chipbench import main
    assert main._tracer(False) is None
    tracing = main._tracer(True)
    try:
        with tracing.span("tm:probe"):
            tracing.count("bytes.d2h", 3)
        assert [sp.name for sp in tracing.records()
                if sp.name != "tm:gc"] == ["tm:probe"]     # gc may run
        assert [c.n for c in tracing.counts()] == [3]
    finally:
        tracing.disable()
        tracing.reset()


@pytest.mark.parametrize("metric, value", [
    ("recovery_s.warmup", 2.0 / 2), ("recovery_s.compile", 1.8 / 2),
    ("recovery_s.state", (4 + 0.125) / 2), ("ckpt_d2h_gbps", 2.0 / 1.1)])
def test_program_span_readers(metric, value):
    assert spec.reader(metric)(_program_run()) == pytest.approx(value)


@pytest.mark.parametrize("calls, gbps", [(4, 2.0 / 1.1), (2, 2.0 / 1.0)])
def test_ckpt_d2h_counts_each_stage_once(calls, gbps):
    """Four calls a tick (the DP replicas sharing a state) and two (one
    state a DP group) copy the same 2 GB: the reader reads both alike."""
    run = _program_run()
    if calls == 2:          # the calls that found the host copy gone
        cached = run.program_spans[13], run.program_spans[15]
        run.program_spans = [sp._replace(name="tm:imc_put")
                             if sp in cached else sp
                             for sp in run.program_spans]
        run.program_counts = [c for c in run.program_counts
                              if c.t not in [sp.end for sp in cached]]
    assert spec.reader("ckpt_d2h_gbps")(run) == pytest.approx(gbps)


@pytest.mark.parametrize("metric", ["recovery_s.warmup", "recovery_s.compile",
                                    "recovery_s.state", "ckpt_d2h_gbps",
                                    "device_idle_frac.step"])
def test_program_span_readers_find_nothing_untraced(metric):
    run = _program_run()
    run.program_spans, run.program_counts = [], []
    assert spec.reader(metric)(run) is None


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


def test_limits_name_every_number():
    from chipbench import compare
    for c in BENCH["configs"]:
        limits = spec.config(c["name"])["limits"]
        assert sorted(limits) == sorted(compare.NUMBERS), c["name"]
        assert all(v > 0 for v in limits.values()), c["name"]


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


def test_each_device_keeps_its_own_clock():
    """Two chips run run id 7 at the same host time, 1100-1600 ns, while
    their clocks read 1000 and 800: the host's events for each device's
    ordinal put each on the host's clock by its own offset."""
    from collections import namedtuple
    Plane = namedtuple("Plane", "name lines")
    Line = namedtuple("Line", "name events")
    Event = namedtuple("Event", "name start_ns duration_ns stats")

    def device(ordinal, start):
        run = Event("jit_fwd(1)", start, 500, [("run_id", 7)])
        return Plane(f"/device:TPU:{ordinal}", [
            Line("XLA Modules", [run]),
            Line("XLA Ops", [run._replace(name="fusion", stats=[])])])

    def host(name, ordinal, t):
        return Event(name, t, 1, [("run_id", 7), ("device_ordinal", ordinal)])

    planes = [device(0, 1000), device(1, 800), Plane("/host:CPU", [
        Line("main", [Event(trace_reduce.WINDOW_SPAN, 0, 10_000, []),
                      host("DoEnqueueProgram", 0, 1090),
                      host("DoEnqueueProgram", 1, 1080)]),
        Line("callbacks", [host("CompleteCallbacks", 0, 1610),
                           host("CompleteCallbacks", 1, 1620)])])]
    t = trace_reduce.from_planes(planes, ())
    assert t.offsets == {"/device:TPU:0": [(1000, 100.0)],
                         "/device:TPU:1": [(800, 300.0)]}
    assert t.ops == {"/device:TPU:0": [(1100, 1600)],
                     "/device:TPU:1": [(1100, 1600)]}
    assert t.programs == [("jit_fwd", 1100, 1600)] * 2
    assert trace_reduce.busy_s(t) == pytest.approx(500e-9)


def test_recorded_tpu_trace(tmp_path):
    """A trace recorded on a TPU v5e: two jitted programs (both named
    `jit__lambda`) run three times, the second inside `ckpt_put` within
    `train`, with a 2 ms sleep after each `train`. The host's enqueues
    and callbacks, paired with the executions by run id, bound the device
    clock's offset to 1.399-1.736 ms."""
    shutil.copy(HERE / "testdata" / "tiny_tpu.xplane.pb", tmp_path)
    t = trace_reduce.load(str(tmp_path), ("train", "ckpt_put"))
    assert t.devices == ["/device:TPU:0"]
    assert len(t.offsets["/device:TPU:0"]) == 1
    assert 1_399_385 <= t.offsets["/device:TPU:0"][0][1] <= 1_736_109
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
