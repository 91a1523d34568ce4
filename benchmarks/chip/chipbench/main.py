"""One run of one cell: set-up, the measured window, the check.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up builds the cell's job from `--seed` (weights and data on the
device), trains step 0 in `bootstrap_job`, reads the program's first
gradient, runs the traffic mix's warm-up actions through the window's
own calls, and copies Adam's master weights to the host. The window
then repeats the mix's actions, whole cycles, until `--seconds` have
passed, and ends when every training machine's state is ready. After
it, device memory is read, the job is freed, and the plain reference
follows the same first steps to decide `correct`.

With `--trace 1` the runtime's own tracer (`repro.core.tracing`) is on
from before the job is built, the window runs under the JAX profiler,
the benchmark's host spans and the runtime's `tm:` spans are written
into the trace, and the per-layer metrics are read from the trace and
the tracer's records; otherwise the end-to-end metrics are reported and
the runtime's tracer stays off. The last line of standard output is the
JSON result.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
import traceback
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax

from chipbench import compare, flops, job, reference, spec, trace_reduce

SPANS = ("train", "ckpt_put", "migration", "failure")


@dataclass
class Run:
    """What a metric reader reads."""
    cell: spec.Cell
    setup_s: float
    window_s: float
    iterations: int
    spans: job.Spans
    window_start: float
    memory_peak_bytes: int
    device_kind: str
    trace: Optional[trace_reduce.Trace] = None
    # the runtime tracer's records (`tracing.records()`, `counts()`) on
    # the clock of `window_start`; empty with `--trace 0`
    program_spans: List[Any] = field(default_factory=list)
    program_counts: List[Any] = field(default_factory=list)

    @property
    def cfg(self) -> dict:
        return self.cell.config

    @property
    def tokens(self) -> int:
        return self.iterations * flops.tokens_per_iteration(self.cfg)

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / self.window_s

    def span_total(self, name: str) -> float:
        return self.spans.total(name, since=self.window_start)

    def span_count(self, name: str) -> int:
        return self.spans.count(name, since=self.window_start)

    def peaks(self) -> Dict[str, float]:
        return spec.peaks(self.device_kind)

    def _in_window(self, a: float, b: float) -> bool:
        return self.window_start <= a and b <= self.window_start \
            + self.window_s

    def program(self, names: Sequence[str], inside: Optional[str] = None
                ) -> List[Any]:
        """The window's program spans named in `names`, and with `inside`
        only those with an ancestor of that name; a span inside another
        of `names` is left out, so no time counts twice."""
        spans, out = self.program_spans, []
        for sp in spans:
            if sp.name not in names or not self._in_window(sp.start,
                                                           sp.end):
                continue
            up, seen = sp.parent, inside is None
            while up >= 0 and spans[up].name not in names:
                seen = seen or spans[up].name == inside
                up = spans[up].parent
            if up < 0 and seen:
                out.append(sp)
        return out

    def program_s(self, names: Sequence[str],
                  inside: Optional[str] = None) -> float:
        return sum(sp.end - sp.start for sp in self.program(names, inside))


def parse(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def device_check(chips: int) -> None:
    """Exit non-zero unless JAX sees at least `chips` TPU chips."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.stderr.write(f"needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)\n")
        raise SystemExit(3)


def _memory_peak() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _tracer(on: bool):
    """The runtime's tracer, reset and turned on; None where `on` is
    false or the runtime has none."""
    if not on:
        return None
    try:
        from repro.core import tracing
    except ImportError:
        return None
    tracing.reset()
    tracing.enable()
    return tracing


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    cfg, traffic = cell.config, cell.traffic
    spans = job.Spans(annotate=trace)
    phases = [("start", time.perf_counter())]
    tracer = _tracer(trace)
    ctl = job.build(cfg, seed)
    phases.append(("build", time.perf_counter()))
    grads = job.first_grads(ctl, cfg["optimizer"]["b1"])
    drv = job.MixRunner(ctl, traffic, spans)
    for action in traffic["warmup"]:
        drv.act(action)
    phases.append(("warmup", time.perf_counter()))
    losses = ctl.engine.losses[:1] + drv.losses
    masters = job.master_weights(ctl)
    phases.append(("readings", time.perf_counter()))
    if trace:
        job.instrument_checkpoint(ctl, spans)
    job.block(ctl)
    gc.collect()

    trace_dir = spec.HERE / ".out" / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # TraceMe spans, no Python calls
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    cycle, attempted, failed = traffic["window"], 0, 0
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        while True:
            attempted += 1
            try:
                drv.act(cycle[(attempted - 1) % len(cycle)])
            except Exception:
                traceback.print_exc()
                failed += 1
                break
            if attempted % len(cycle) == 0 and \
                    time.perf_counter() - t0 >= seconds:
                break
        if not failed:
            job.block(ctl)
        t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    iterations = spans.count("train", since=t0)
    downtimes = [r.downtime for r in ctl.reports]
    run = Run(cell, setup_s, t1 - t0, iterations, spans, t0,
              _memory_peak(), jax.devices()[0].device_kind)
    if tracer is not None:
        run.program_spans, run.program_counts = (tracer.records(),
                                                 tracer.counts())
        tracer.disable()
        tracer.reset()
    if trace:
        run.trace = trace_reduce.load(str(trace_dir), SPANS)
    del drv, ctl
    gc.collect()

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics:
        if m.kind == kind:
            value = spec.reader(m.name)(run)
            if value is not None:
                metrics[m.name] = {"value": value, "unit": m.unit}

    t_ref = time.perf_counter()
    follow = reference.follow(cfg, seed, len(losses), "reference")
    numbers = compare.gaps(
        compare.program_readings(losses, grads, masters, follow, cfg),
        compare.follow_readings(follow, cfg))
    correct = failed == 0 and compare.judge(numbers, cfg["limits"])

    sys.stderr.write(
        f"set-up s: imports {phases[0][1] - t_start!r} " + " ".join(
            f"{n} {b - a!r}" for (_, a), (n, b) in zip(phases, phases[1:]))
        + f"; window s {t1 - t0!r}; reference s "
        f"{time.perf_counter() - t_ref!r}\n")
    sys.stderr.write("window actions s: " + " ".join(
        f"{n}:{b - a:.3f}" for n, a, b in spans.items if a >= t0) + "\n")
    if downtimes:
        sys.stderr.write("recoveries' downtime, SimClock s (modelled): "
                         + " ".join(repr(d) for d in downtimes) + "\n")
    sys.stderr.write(f"losses program {losses!r} reference "
                     f"{follow['losses']!r}\n")
    sys.stderr.write("\n".join(compare.lines(numbers, cfg["limits"]))
                     + "\n")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_reduce.busy_s(run.trace)
        device["window_s"] = trace_reduce.window_s(run.trace)
        out["breakdown"] = {
            "device_ops": trace_reduce.top_programs(run.trace),
            "idle_gaps": trace_reduce.idle_gaps(run.trace)}
    out["checks"] = {k: {"value": numbers[k],
                         "limit": cfg["limits"].get(k)}
                     for k in compare.NUMBERS}
    return out


def main(argv: Optional[List[str]] = None, t_start: float = 0.0) -> None:
    args = parse(argv)
    cell = spec.cell(args.workload)
    device_check(cell.chips)
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start)
    print(json.dumps(result))
