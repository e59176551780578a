"""Reduce a profiler trace to the numbers the benchmark reports.

The trace is read once into plain events (plane, line, name, start,
duration; nanoseconds on the profiler's one clock), so that every
reduction below works the same on a trace recorded on the chip and on
the small recorded trace the tests keep.

- Device planes are those named `/device:TPU:<n>`. Their `XLA Ops` line
  holds one event per executed operation, named by its HLO instruction
  (ops inside a loop nest in the loop's event), their `XLA Modules` line
  one per executed program (`jit_<function>(<hash>)`).
- Host spans are the benchmark's own `TraceAnnotation`s (`chipbench.*`,
  `executor.*`) on the host plane.
- The window is the host span `chipbench.window`; every device interval
  is clipped to it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "chipbench.window"
HOST_SPAN = re.compile(r"^(chipbench|executor)\.")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: int          # ns
    dur: int            # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


def read_xplane(path: str) -> List[Event]:
    """Device op and module events and the benchmark's host spans of one
    `.xplane.pb` file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        if not dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if dev or HOST_SPAN.match(e.name):
                    out.append(Event(plane.name, line.name, e.name,
                                     int(e.start_ns), int(e.duration_ns)))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def save_events(events: Sequence[Event], path: Path):
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_events(path: Path) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*e) for e in json.load(f)]


# -- intervals ----------------------------------------------------------

def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


# -- reductions -------------------------------------------------------

@dataclasses.dataclass
class Reduced:
    window: Tuple[int, int]                 # ns, on the trace's clock
    devices: List[str]
    busy_s: float                           # mean over devices
    op_s: Dict[str, float]                  # "program:op" -> own seconds
    module_s: Dict[str, float]              # program name -> seconds
    module_n: Dict[str, int]                # program name -> executions
    gaps: List[Tuple[str, float]]           # idle gaps by host activity,
    #                                         longest first; gaps under
    #                                         SHORT_GAP_NS summed as one

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def ops_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_s.items() if rx.search(n))

    def modules_matching(self, pattern: str) -> Tuple[float, int]:
        rx = re.compile(pattern)
        keys = [n for n in self.module_s if rx.search(n)]
        return (sum(self.module_s[k] for k in keys),
                sum(self.module_n[k] for k in keys))

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        idle: Dict[str, float] = defaultdict(float)
        for name, s in self.gaps:
            idle[name] += s
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def window_of(events: Sequence[Event]) -> Tuple[int, int]:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(spans)}")
    return spans[0].start, spans[0].end


SHORT_GAP_NS = 50_000       # gaps shorter than this are op boundaries


class _HostActivity:
    """What the host was doing at a time: the benchmark span open then
    (executor calls and sleeps do not overlap), else `engine`: the
    scheduler's own host work inside the window."""

    def __init__(self, spans: Sequence[Event]):
        self.spans = sorted((e for e in spans if e.name != WINDOW_SPAN),
                            key=lambda e: e.start)
        self.starts = [e.start for e in self.spans]

    def at(self, t: int) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and t < self.spans[i].end:
            return self.spans[i].name
        return "engine"


def op_name(event_name: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (`%fusion.134 = bf16[...] fusion(...)`): keep the instruction's name."""
    return event_name.split(" = ", 1)[0]


def program_name(event_name: str) -> str:
    """`jit_decode_fn(3601732022010702206)` -> `jit_decode_fn`."""
    return event_name.split("(", 1)[0]


def self_times(ops: Sequence[Event]) -> List[Tuple[Event, int]]:
    """Each op event with its own time: its duration less that of the op
    events nested in it (a `while` holds its body's ops)."""
    out: List[List] = []
    stack: List[List] = []
    for e in sorted(ops, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        item = [e, e.dur]
        if stack:
            stack[-1][1] -= e.dur
        stack.append(item)
        out.append(item)
    return [(e, max(t, 0)) for e, t in out]


def reduce(events: Sequence[Event]) -> Reduced:
    lo, hi = window_of(events)
    devices = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})
    if not devices:
        raise RuntimeError("the trace has no device plane")
    op_s: Dict[str, float] = defaultdict(float)
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    busy = []
    gaps = []
    host = _HostActivity([e for e in events if e.plane == HOST_PLANE
                          and e.end > lo and e.start < hi])
    for dev in devices:
        mods = sorted((e for e in events
                       if e.plane == dev and e.line == MODULES_LINE
                       and e.end > lo and e.start < hi),
                      key=lambda e: e.start)
        for e in mods:
            name = program_name(e.name)
            module_s[name] += total(clip([(e.start, e.end)], lo, hi)) / 1e9
            module_n[name] += 1
        starts = [e.start for e in mods]
        ops = [e for e in events if e.plane == dev and e.line == OPS_LINE
               and e.end > lo and e.start < hi]
        for e, own in self_times(ops):
            i = bisect.bisect_right(starts, e.start) - 1
            prog = program_name(mods[i].name) if i >= 0 else "?"
            share = total(clip([(e.start, e.end)], lo, hi)) / max(e.dur, 1)
            op_s[f"{prog}:{op_name(e.name)}"] += own * share / 1e9
        iv = clip(union((e.start, e.end) for e in ops), lo, hi)
        busy.append(total(iv))
        edges = [lo] + [t for s, e in iv for t in (s, e)] + [hi]
        short = 0
        for s, e in zip(edges[::2], edges[1::2]):
            if e - s >= SHORT_GAP_NS:
                gaps.append((host.at((s + e) // 2), (e - s) / 1e9))
            else:
                short += e - s
        gaps.append(("op boundaries", short / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced((lo, hi), devices, sum(busy) / len(devices) / 1e9,
                   dict(op_s), dict(module_s), dict(module_n), gaps)
