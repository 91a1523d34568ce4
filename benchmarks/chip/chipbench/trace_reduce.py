"""From a profiler trace to the benchmark's device numbers.

`load` reads the `.xplane.pb` the JAX profiler writes into a `Trace`:
the device's operation intervals, its program executions, and the host
spans (TraceAnnotations) of the benchmark and of the runtime's tracer
(named `tm:*`) on the same clock. The reductions below work on a
`Trace` alone, so they are checked on a small hand-made one.

- busy: the union of the device's operation intervals inside the
  window (or inside the host spans of one name), averaged over the
  devices;
- program time: the summed device durations of the executions of the
  programs whose names match;
- idle gaps: the stretches of the window in which no operation ran on a
  device, each labelled by the innermost host span that covers its
  middle ("none" where no span does).

Each device's clock is put on the host's by the run id that the host's
`DoEnqueueProgram` and `CompleteCallbacks` for that device's ordinal
and the device's program execution each carry: an execution starts
after the host enqueued it and ends before the host runs its callbacks,
which bounds the offset between the two clocks from both sides. The
offset drifts and at times jumps over a long trace, so each device's
executions are taken in order and cut into segments whose bounds agree;
each segment takes the middle of its bounds, and an operation the
offset of its device's segment it starts in.
"""
from __future__ import annotations

import bisect
import glob
import math
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
    # device -> [(device time from which it holds, ns added to that
    # device's timestamps)]
    offsets: Dict[str, List[Tuple[float, float]]] = field(
        default_factory=dict)


WINDOW_SPAN = "bench_window"
PROGRAM_SPANS = "tm:"           # prefix of the runtime tracer's spans
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
_ID = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """`jit_fwd(1234)` -> `jit_fwd`."""
    return _ID.sub("", event_name.strip())


def _stat(event, key: str) -> Optional[int]:
    for name, value in event.stats:
        if name == key:
            return value
    return None


def _ordinal(plane_name: str) -> Optional[int]:
    """`/device:TPU:3` -> 3."""
    m = re.search(r":(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def load(trace_dir: str, span_names: Sequence[str]) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_planes(ProfileData.from_file(paths[-1]).planes, span_names)


def from_planes(planes, span_names: Sequence[str]) -> Trace:
    """A `Trace` from the planes of a profile (`ProfileData.planes`, or
    objects with the same `name`, `lines` and events)."""
    ops: Dict[str, List[Interval]] = {}
    programs: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    runs: Dict[str, Dict[int, Interval]] = {}   # device -> run id -> run
    # (device ordinal, run id) -> the host's earliest enqueue / callback
    enqueued: Dict[Tuple[Optional[int], int], float] = {}
    completed: Dict[Tuple[Optional[int], int], float] = {}
    wanted = set(span_names) | {WINDOW_SPAN}
    for plane in planes:
        if plane.name.startswith("/device:"):
            dev_ops = ops.setdefault(plane.name, [])
            dev_programs = programs.setdefault(plane.name, [])
            dev_runs = runs.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev_ops += [(e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events]
                elif line.name == "XLA Modules":
                    for e in line.events:
                        iv = (e.start_ns, e.start_ns + e.duration_ns)
                        dev_programs.append((program_name(e.name), *iv))
                        rid = _stat(e, "run_id")
                        if rid is not None:
                            dev_runs[rid] = iv
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted or e.name.startswith(
                            PROGRAM_SPANS):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name in (ENQUEUE, COMPLETE):
                        seen = enqueued if e.name == ENQUEUE else completed
                        key = (_stat(e, "device_ordinal"),
                               _stat(e, "run_id"))
                        seen[key] = min(seen.get(key, e.start_ns),
                                        e.start_ns)
    offsets = {dev: clock_offsets(runs[dev],
                                  _of_device(enqueued, _ordinal(dev)),
                                  _of_device(completed, _ordinal(dev)))
               for dev in runs}

    def shift(dev, a, b):
        off = _offset(offsets[dev], a)
        return a + off, b + off

    ops = {dev: [shift(dev, a, b) for a, b in v]
           for dev, v in ops.items() if v}
    shifted = [(n, *shift(dev, a, b))
               for dev, v in programs.items() for n, a, b in v]
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    window = (win[0][1], win[0][2])
    return Trace(ops, shifted, [s for s in spans if s[0] != WINDOW_SPAN],
                 window, sorted(ops), {d: offsets[d] for d in sorted(ops)})


def _of_device(seen: Dict[Tuple[Optional[int], int], float],
               ordinal: Optional[int]) -> Dict[int, float]:
    """{run id: host time} of one device's events; an event that names
    no device counts for every device."""
    out: Dict[int, float] = {}
    for (dev, rid), t in seen.items():
        if dev is None or dev == ordinal:
            out[rid] = min(out.get(rid, t), t)
    return out


def _middle(lo: float, hi: float) -> float:
    if math.isfinite(lo) and math.isfinite(hi):
        return (lo + hi) / 2
    return lo if math.isfinite(lo) else hi if math.isfinite(hi) else 0.0


def clock_offsets(runs: Dict[int, Interval], enqueued: Dict[int, float],
                  completed: Dict[int, float]) -> List[Tuple[float, float]]:
    """[(device time from which it holds, nanoseconds to add to device
    timestamps to put them on the host's clock)]: the executions in
    order of start, cut where the bounds of the next one leave none in
    common with the segment's so far."""
    out: List[Tuple[float, float]] = []
    lo, hi, start = -math.inf, math.inf, None
    for rid in sorted(runs, key=lambda r: runs[r]):
        a, b = runs[rid]
        lo_r = enqueued[rid] - a if rid in enqueued else -math.inf
        hi_r = completed[rid] - b if rid in completed else math.inf
        if start is not None and max(lo, lo_r) > min(hi, hi_r):
            out.append((start, _middle(lo, hi)))
            lo, hi, start = -math.inf, math.inf, None
        lo, hi = max(lo, lo_r), min(hi, hi_r)
        start = a if start is None else start
    if start is not None:
        out.append((start, _middle(lo, hi)))
    return out


def _offset(offsets: List[Tuple[float, float]], t: float) -> float:
    if not offsets:
        return 0.0
    i = bisect.bisect_right(offsets, (t, math.inf)) - 1
    return offsets[max(i, 0)][1]


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


def busy_within(trace: Trace, name: str) -> Tuple[float, float]:
    """(busy seconds averaged over the devices, seconds) of the host
    spans called `name` that lie inside the window."""
    lo, hi = trace.window
    inside = [(a, b) for n, a, b in trace.spans
              if n == name and lo <= a and b <= hi]
    if not trace.ops or not inside:
        return 0.0, 0.0
    busy = sum(sum(y - x for x, y in _union(v, iv))
               for iv in inside for v in trace.ops.values())
    return busy / len(trace.ops) / 1e9, sum(b - a for a, b in inside) / 1e9


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
