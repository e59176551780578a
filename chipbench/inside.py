"""What the program marks from inside, in a traced window: device time by
named scope, the program's host spans, and its counters.

`run.py` reduces a trace to programs and HLO ops (`trace_reduce.py`) and
reads no counter of the program's. This module reads the rest:

- Device ops by the named scope they ran under. A TPU op event holds its
  HLO instruction and no metadata, so each is looked up in the step
  programs compiled as they ran (`op_names`, `with_op_names`), whose
  metadata gives its `op_name` path, such as
  `jit(decode_fn)/while/body/closed_call/attention/kv_write/scatter`;
  the scope path is what remains without the jit, loop and call levels
  and the primitive: `attention/kv_write` (`scope_path`). Ops with none
  are `unscoped`.
- The program's host spans (`executor.<kind>.<phase>`, `engine.*`;
  `repro/serving/tracing.py`) beside the benchmark's. An idle gap is
  named by the innermost span open at its middle.
- The program's counters over the window, read as the operator's meter
  reads them: `parse_prometheus` of the registry's text at the two
  scrapes the window opens and closes with.

The per-layer readers that read these (`metrics/decode_attention_ms.py`,
`decode_moe_ms`, `prefill_attention_ms`, `prefill_moe_ms`,
`moe_dispatch_share`, `prefill_useful_share`, `decode_launch_ms`) find
them as `run.trace.scope_s`, `run.trace.span_s` and `run.counters`, and
return None where a run has none, as every run of `run.py` has.

    python3 chipbench/inside.py --workload <cell> --seed <n> --seconds <s>

serves one traced window of a cell as `run.py --trace 1` does, on the
chip this process holds, without the reference check, and prints one
JSON line: every per-layer metric of the cell and these seven, the top
scopes and idle gaps, and the cross-checks between them.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import gzip
import json
import re
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import trace_reduce as T  # noqa: E402
from chipbench.traffic import prefill_buckets  # noqa: E402

HOST_SPAN = re.compile(r"^(chipbench|executor|engine)\.")
# op_name levels that are not named scopes: those loops and calls add,
# and nested functions (`jit(cumsum)`, an einsum's `gsd,de->gse`), whose
# levels below belong to the scope the function was called in
LOOP_LEVEL = re.compile(r"^(while|body|cond|closed_call|core_call|"
                        r"checkpoint|remat|branch_\d+)$")
NESTED = re.compile(r"\(|->")
UNSCOPED = "unscoped"
READERS = ("decode_attention_ms", "decode_moe_ms", "prefill_attention_ms",
           "prefill_moe_ms", "moe_dispatch_share", "prefill_useful_share",
           "decode_launch_ms")


@dataclasses.dataclass(frozen=True)
class ScopedEvent(T.Event):
    scope: str = ""             # a device op's op_name path


def read_xplane(path: str) -> List[ScopedEvent]:
    """Device op and module events, and the benchmark's and the program's
    host spans, of one `.xplane.pb` file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = bool(T.DEVICE_PLANE.match(plane.name))
        if not dev and plane.name != T.HOST_PLANE:
            continue
        for line in plane.lines:
            if dev and line.name not in (T.OPS_LINE, T.MODULES_LINE):
                continue
            for e in line.events:
                if dev or HOST_SPAN.match(e.name):
                    out.append(ScopedEvent(plane.name, line.name, e.name,
                                           int(e.start_ns),
                                           int(e.duration_ns)))
    return out


HLO_LINE = re.compile(r"\s*(?:ROOT )?(%\S+) = ")
COMPUTATION = re.compile(r"(?:ENTRY )?(%\S+) .*\{$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLS = re.compile(r"calls=(%[\w.\-]+)")


def op_names(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """Instruction name -> (its line, its `op_name`) of a compiled
    program's HLO text. A fusion the compiler left without metadata
    takes the first `op_name` inside the computation it calls."""
    out = {}
    first: Dict[str, str] = {}          # computation -> first op_name
    comp = ""
    for line in hlo_text.splitlines():
        m = HLO_LINE.match(line)
        if not m:
            c = COMPUTATION.match(line)
            comp = c.group(1) if c else comp
            continue
        found = OP_NAME.search(line)
        name = found.group(1) if found else ""
        if name:
            first.setdefault(comp, name)
        callee = CALLS.search(line)
        out[m.group(1)] = (line[m.start(1):], name,
                           callee.group(1) if callee else "")
    return {k: (line, name or (callee and first.get(callee, "")))
            for k, (line, name, callee) in out.items()}


def with_op_names(events: Sequence[ScopedEvent],
                  programs: Dict[str, List[Dict[str, Tuple[str, str]]]]
                  ) -> List[ScopedEvent]:
    """The device ops with the `op_name` of their instruction in the
    program that ran them. `programs` holds each jitted function's
    compiled programs (`jit_prefill_fn`: one a prompt length), by
    `op_names`. Which of them an execution ran is the one whose
    instructions its ops' events begin, HLO text for HLO text."""
    out = [e for e in events if e.line != T.OPS_LINE]
    for dev in sorted({e.plane for e in events if e.line == T.OPS_LINE}):
        mods = sorted((e for e in events if e.plane == dev
                       and e.line == T.MODULES_LINE), key=lambda e: e.start)
        starts = [m.start for m in mods]
        by_run: Dict[int, List[ScopedEvent]] = defaultdict(list)
        for e in events:
            if e.plane == dev and e.line == T.OPS_LINE:
                by_run[bisect.bisect_right(starts, e.start) - 1].append(e)
        for i, ops in by_run.items():
            cands = programs.get(T.program_name(mods[i].name), []) \
                if i >= 0 else []

            def fits(prog):
                return sum(1 for e in ops
                           if prog.get(T.op_name(e.name), ("",))[0]
                           .startswith(e.name))
            prog = max(cands, key=fits) if len(cands) > 1 else \
                (cands or [{}])[0]
            out.extend(dataclasses.replace(
                e, scope=prog.get(T.op_name(e.name), ("", ""))[1])
                for e in ops)
    return out


def load_events(path: Path) -> List[ScopedEvent]:
    """Events `trace_reduce.save_events` wrote, with or without scopes."""
    with gzip.open(path, "rt") as f:
        return [ScopedEvent(*e) for e in json.load(f)]


def scope_path(op_name: str) -> str:
    """`jit(f)/while/body/closed_call/moe/route/jit(cumsum)/x/add` ->
    `moe/route`: the named scopes an op's `op_name` holds above its
    primitive."""
    scopes: List[str] = []
    for level in op_name.split(";")[0].split("/")[:-1]:
        if NESTED.search(level):
            if scopes:
                break
        elif level and not LOOP_LEVEL.match(level):
            scopes.append(level)
    return "/".join(scopes)


# -- reduction ------------------------------------------------------------

class Activity:
    """The innermost host span open at a time (spans nest: the benchmark's
    `executor.decode` holds the program's `executor.decode.launch`), else
    `engine`: the scheduler's own host work."""

    def __init__(self, spans: Sequence[T.Event]):
        self.spans = sorted((e for e in spans if e.name != T.WINDOW_SPAN),
                            key=lambda e: (e.start, -e.dur))
        self.starts = [e.start for e in self.spans]
        self.longest = max((e.dur for e in self.spans), default=0)

    def at(self, t: int) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.starts[i] >= t - self.longest:
            if t < self.spans[i].end:
                return self.spans[i].name
            i -= 1
        return "engine"


@dataclasses.dataclass
class Inside:
    scope_s: Dict[str, float]           # "program:scope path" -> own s
    span_s: Dict[str, Tuple[float, int]]  # host span -> (s, count), the
    #                                       spans wholly inside the window
    gaps: List[Tuple[str, float]]       # idle gaps by innermost span

    def breakdown(self, n: int = 10) -> dict:
        idle: Dict[str, float] = defaultdict(float)
        for name, s in self.gaps:
            idle[name] += s
        def top(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:n]]
        return {"scopes": top(self.scope_s), "idle_gaps": top(idle)}


def reduce(events: Sequence[ScopedEvent]) -> Inside:
    """Own device time by program and scope, host spans and idle gaps in
    the window (`chipbench.window`), as `trace_reduce.reduce` clips and
    attributes op time."""
    lo, hi = T.window_of(events)
    devices = sorted({e.plane for e in events
                      if T.DEVICE_PLANE.match(e.plane)})
    if not devices:
        raise RuntimeError("the trace has no device plane")
    host = [e for e in events if e.plane == T.HOST_PLANE
            and e.name != T.WINDOW_SPAN]
    span_s: Dict[str, List] = defaultdict(lambda: [0.0, 0])
    for e in host:
        if lo <= e.start and e.end <= hi:
            span_s[e.name][0] += e.dur / 1e9
            span_s[e.name][1] += 1
    activity = Activity([e for e in host if e.end > lo and e.start < hi])
    scope_s: Dict[str, float] = defaultdict(float)
    gaps = []
    for dev in devices:
        inside = [e for e in events if e.plane == dev
                  and e.end > lo and e.start < hi]
        mods = sorted((e for e in inside if e.line == T.MODULES_LINE),
                      key=lambda e: e.start)
        starts = [e.start for e in mods]
        ops = [e for e in inside if e.line == T.OPS_LINE]
        for e, own in T.self_times(ops):
            i = bisect.bisect_right(starts, e.start) - 1
            prog = T.program_name(mods[i].name) if i >= 0 else "?"
            share = T.total(T.clip([(e.start, e.end)], lo, hi)) / max(e.dur, 1)
            path = scope_path(e.scope) or UNSCOPED
            scope_s[f"{prog}:{path}"] += own * share / 1e9
        busy = T.clip(T.union((e.start, e.end) for e in ops), lo, hi)
        edges = [lo] + [t for s, e in busy for t in (s, e)] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e - s >= T.SHORT_GAP_NS:
                gaps.append((activity.at((s + e) // 2), (e - s) / 1e9))
    return Inside(dict(scope_s), {k: (v[0], v[1]) for k, v in span_s.items()},
                  sorted(gaps, key=lambda g: -g[1]))


# -- what the readers share -------------------------------------------------

def scope_seconds(run, program: str, top: str) -> Dict[str, float]:
    """Own device seconds of the ops of programs matching `program` under
    the top-level scope `top`, by scope path; empty where the run has no
    scopes."""
    scopes = getattr(run.trace, "scope_s", None) or {}
    rx = re.compile(program)
    out: Dict[str, float] = defaultdict(float)
    for key, secs in scopes.items():
        prog, path = key.split(":", 1)
        if rx.search(prog) and path.split("/")[0] == top:
            out[path] += secs
    return dict(out)


def per_call_ms(run, kind: str, top: str) -> Optional[float]:
    """Device ms under scope `top` in the `<kind>_fn` program, per call of
    that kind in the traced window (as `decode_step_ms` counts calls)."""
    secs = sum(scope_seconds(run, f"{kind}_fn", top).values())
    calls = len(run.traced_steps(kind)) if run.trace is not None else 0
    if not calls or not secs:
        return None
    return 1e3 * secs / calls


# -- one traced window on the chip ------------------------------------------

def serve_inside(cell_name: str, seed: int, seconds: float, log=print, *,
                 trace: bool = True,
                 bench_path: Path = ROOT / "BENCHMARK.json", devices=None):
    """Serve one window of a cell on the chip this process holds, as
    `run.py` does: (what `run.serve` returned, with `run.counters` and,
    traced, `run.trace.scope_s` and `.span_s` filled; the reduction, or
    None; the scoped events; the two scrapes, each the counters and the
    number of steps recorded by then)."""
    from chipbench import run as bench
    from repro.launch.compile_cache import use_compile_cache
    from repro.serving.metrics import parse_prometheus
    _, cell, config, mix = bench.load_cell(cell_name, bench_path)
    use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = (devices or jax.devices())[0]
    if dev.platform != "tpu":
        raise bench.NoChip(f"needs a TPU; JAX's first device is "
                           f"{dev.platform!r}")
    peaks = bench.peaks_for(dev.device_kind)
    scrapes = []
    built = []

    def make_engine(cj, seed):
        eng = bench.build(cj, seed)
        built.append((eng.ex, eng.cfg))
        render = eng.metrics.render

        def scraped():
            text = render()
            scrapes.append((parse_prometheus(text),
                            len(getattr(eng.ex, "steps", ()))))
            return text
        eng.metrics.render = scraped
        return eng

    # `run.serve` reads the trace and deletes it: read it here as well
    events: List[ScopedEvent] = []
    plain = T.read_xplane

    def read_both(path):
        events.extend(read_xplane(path))
        return plain(path)

    T.read_xplane = read_both
    try:
        served = bench.serve(cell, config, mix, peaks, seed=seed,
                             seconds=seconds, trace=trace,
                             make_engine=make_engine, log=log)
    finally:
        T.read_xplane = plain
    run = served.run
    (c0, _), (c1, _) = scrapes[0], scrapes[-1]
    run.counters = {k: v - c0.get(k, 0.0) for k, v in c1.items()}
    inside = None
    if trace:
        t0 = time.perf_counter()
        ex, ecfg = built[0]
        events = with_op_names(events, step_programs(
            ex, ecfg, prefill_buckets(mix)))
        log(f"inside: op names of the step programs in "
            f"{time.perf_counter() - t0:.1f}s")
        inside = reduce(events)
        run.trace.scope_s, run.trace.span_s = inside.scope_s, inside.span_s
    return served, inside, events, scrapes


def step_programs(ex, ecfg, buckets) -> Dict[str, List[dict]]:
    """`op_names` of the executor's decode program and of its prefill
    program at each prompt length, compiled for the shapes it ran."""
    import jax
    import jax.numpy as jnp
    B, P = ecfg.max_batch, ecfg.max_pages_per_seq

    def shape(*s, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(s, dtype)
    state = jax.tree.map(lambda a: shape(*a.shape, dtype=a.dtype),
                         (ex.params, ex.pools))
    lowered = {"jit_decode_fn": [ex.decode_fn.lower(
        *state, shape(B, P), shape(B), shape(B), shape(B, dtype=bool))]}
    lowered["jit_prefill_fn"] = [ex.prefill_fn.lower(
        *state, shape(B, P), shape(B), shape(B, n), shape(B),
        shape(B, dtype=bool)) for n in buckets]
    return {k: [op_names(lo.compile().as_text()) for lo in v]
            for k, v in lowered.items()}


def summary(served, inside: Inside, scrapes, bench_json: dict) -> dict:
    """The result line of `main`."""
    from chipbench import run as bench
    run = served.run
    names = [m["name"] for m in bench.cell_metrics(bench_json, run.cell,
                                                    True)] + list(READERS)
    metrics = {n: bench.reader(n)(run) for n in names}
    scoped = {}
    for prog, secs in run.trace.module_s.items():
        own = sum(s for k, s in inside.scope_s.items()
                  if k.split(":", 1)[0] == prog
                  and not k.endswith(":" + UNSCOPED))
        scoped[prog] = 100 * own / secs if secs else None
    (_, n0), (_, n1) = scrapes[0], scrapes[-1]
    prompt = sum(sum(s.new_tokens) for s in run.steps[n0:n1]
                 if s.kind == "prefill")
    return {
        "setup_s": run.setup_s, "compiles_in_window": served.compiles,
        "metrics": metrics,
        "scoped_share_pct": scoped,
        "prompt_tokens": {
            "counter": run.counters.get("repro:prompt_tokens_total"),
            "steps_between_scrapes": prompt},
        "counters": {k: v for k, v in run.counters.items()
                     if k.endswith("_total")},
        "spans": {k: list(v) for k, v in sorted(inside.span_s.items())},
        "breakdown": inside.breakdown(),
    }


def main(argv=None) -> int:
    from chipbench import run as bench
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        served, inside, _, scrapes = serve_inside(
            args.workload, args.seed, args.seconds, log)
    except bench.NoChip as e:
        log(f"chipbench: {e}")
        return 3
    bench_json = json.loads((ROOT / "BENCHMARK.json").read_text())
    print(json.dumps(summary(served, inside, scrapes, bench_json)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
