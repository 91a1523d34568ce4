"""From a profiler trace to the benchmark's device numbers.

`load` reads the `.xplane.pb` the JAX profiler writes into a `Trace`:
the device's operation intervals, its program executions, and the
benchmark's own host spans (TraceAnnotations) on the same clock. The
reductions below work on a `Trace` alone, so they are checked on a small
hand-made one.

- busy: the union of the device's operation intervals inside the
  window, averaged over the devices;
- program time: the summed device durations of the executions of the
  programs whose names match;
- idle gaps: the stretches of the window in which no operation ran on a
  device, each labelled by the innermost host span that covers its
  middle ("none" where no span does).

The device's clock is put on the host's: the k-th program execution on
the device is the k-th `PJRT_LoadedExecutable_Execute` launched on the
host and ends before the host's k-th `tpu::System::Execute=>Done`, which
bounds the offset between the two clocks from both sides; the middle of
those bounds is taken.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start, end) in ns


@dataclass
class Trace:
    ops: Dict[str, List[Interval]]                       # device -> ops
    programs: List[Tuple[str, float, float]]             # name, start, end
    spans: List[Tuple[str, float, float]]                # name, start, end
    window: Interval
    devices: List[str] = field(default_factory=list)
    offset_ns: float = 0.0          # added to the device's timestamps


WINDOW_SPAN = "bench_window"
LAUNCH, DONE = "PJRT_LoadedExecutable_Execute", "tpu::System::Execute=>Done"
_ID = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """`jit_fwd(1234)` -> `jit_fwd`."""
    return _ID.sub("", event_name.strip())


def load(trace_dir: str, span_names: Sequence[str]) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops: Dict[str, List[Interval]] = {}
    programs: List[Tuple[str, float, float]] = []
    spans: List[Tuple[str, float, float]] = []
    launches: List[float] = []
    dones: List[float] = []
    wanted = set(span_names) | {WINDOW_SPAN}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev_ops = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev_ops += [(e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events]
                elif line.name == "XLA Modules":
                    programs += [(program_name(e.name), e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name == LAUNCH:
                        launches.append(e.start_ns)
                    elif e.name == DONE:
                        dones.append(e.start_ns + e.duration_ns)
    ops = {k: v for k, v in ops.items() if v}
    off = clock_offset(sorted(launches), sorted(dones),
                       sorted((a, b) for _, a, b in programs))
    ops = {k: [(a + off, b + off) for a, b in v] for k, v in ops.items()}
    programs = [(n, a + off, b + off) for n, a, b in programs]
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    window = (win[0][1], win[0][2])
    return Trace(ops, programs, [s for s in spans if s[0] != WINDOW_SPAN],
                 window, sorted(ops), off)


def clock_offset(launches: List[float], dones: List[float],
                 executions: List[Interval]) -> float:
    """Nanoseconds to add to device timestamps to put them on the host's
    clock; 0 where launches, completions and executions do not pair one
    to one (several devices) or the bounds disagree."""
    if not executions or not (len(launches) == len(dones)
                              == len(executions)):
        return 0.0
    lo = max(h - d[0] for h, d in zip(launches, executions))
    hi = min(h - d[1] for h, d in zip(dones, executions))
    return (lo + hi) / 2 if lo <= hi else 0.0


def _union(intervals: List[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    out: List[Interval] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_s(trace: Trace) -> float:
    """Seconds with an operation running, averaged over the devices."""
    if not trace.ops:
        return 0.0
    tot = sum(sum(b - a for a, b in _union(v, trace.window))
              for v in trace.ops.values())
    return tot / len(trace.ops) / 1e9


def window_s(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) / 1e9


def _inside(t: float, spans: List[Tuple[str, float, float]],
            names: Optional[Sequence[str]]) -> bool:
    return any(a <= t <= b for n, a, b in spans
               if names is None or n in names)


def program_s(trace: Trace, pattern: str,
              within: Optional[Sequence[str]] = None) -> Tuple[float, int]:
    """(seconds, executions) of the programs whose name matches
    `pattern` and that start inside the window (and, with `within`,
    inside one of those host spans)."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    tot, n = 0.0, 0
    for name, a, b in trace.programs:
        if rx.fullmatch(name) and lo <= a <= hi and (
                within is None or _inside(a, trace.spans, within)):
            tot += b - a
            n += 1
    return tot / 1e9, n


def top_programs(trace: Trace, top: int = 10) -> List[List]:
    lo, hi = trace.window
    tot: Dict[str, float] = {}
    for name, a, b in trace.programs:
        if lo <= a <= hi:
            tot[name] = tot.get(name, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            ][:top]


def label(t: float, spans: List[Tuple[str, float, float]]) -> str:
    """The innermost (latest-starting) span covering time t."""
    cover = [(a, n) for n, a, b in spans if a <= t <= b]
    return max(cover)[1] if cover else "none"


def idle_gaps(trace: Trace, top: int = 10) -> List[List]:
    """The longest idle stretches of the first device, labelled."""
    if not trace.ops:
        return []
    busy = _union(trace.ops[trace.devices[0]], trace.window)
    edges = [trace.window[0]] + [x for iv in busy for x in iv] \
        + [trace.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label((a + b) / 2, trace.spans), (b - a) / 1e9]
            for a, b in gaps[:top]]
