"""Run one cell of BENCHMARK.json once, on the chip this process holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration (`chipbench/configs/<config>.json`, the
file BENCHMARK.json gives) and a traffic mix (`chipbench/traffic/
<traffic>.json`); its correctness limit is `chipbench/limits/<cell>.json`
and each metric's reader is `chipbench/metrics/<metric>.py`. A new cell,
mix or metric is new files and entries; no file here names one.

Set-up (`setup_s`, from process start to the window's opening): the
engine `repro.launch.serve.build_engine` builds for the real tier, with
weights drawn on the device from the seed, the requests from the seed, one call of every
prefill length the mix can form and of the decode step, then serving on
the wall clock until the window opens: at `warm_s` of an open loop, or
once every slot holds a request under a backlog. The window then serves
for `--seconds` on the wall clock (`chipbench/wallclock.py`); nothing
compiles inside it. With `--trace 1` the profiler records the window and
the per-layer metrics are read from it; otherwise the end-to-end ones.

After the window: the device's peak memory is read, the program's state
is freed, and a seeded sample of the requests the window finished (the
longest among them) is checked against the float32 reference of the
configuration's architecture (`chipbench/arch.py`; by default
`chipbench/reference.py`): for each served token, the gap by which its
reference logit lies below the reference's best there. `correct` holds
where the gap statistics the cell's limits file names are within their
limits, and the exact checks pass. The last
line of stdout is one JSON object; the numbers compared, with their
limits, come last in it and on stderr.

Exits 3, printing no result, unless JAX's first device is a TPU and
there are as many devices as the cell asks for chips.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from chipbench import arch, trace_reduce  # noqa: E402
from chipbench.record import Run  # noqa: E402
from chipbench.traffic import load_mix, make_traffic  # noqa: E402
from chipbench.wallclock import WallClock  # noqa: E402

SAMPLE_TOKENS = 512         # served tokens the correctness sample reaches
SAMPLE_MIN = 4              # requests it takes at least, where finished
SAMPLE_MAX = 32             # requests it takes at most
DRAIN_S = 60.0              # an open loop waits this long past the close
#                             for first tokens of requests due in the window


class NoChip(RuntimeError):
    """The device this process found cannot run the cell."""


class CompileCounter:
    """Counts lowerings to MLIR while active: one per jit-cache miss of a
    jitted function or an eager op, whether XLA then compiles it or the
    persistent cache supplies it."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.n = 0

    def __call__(self, event, duration_secs, **kwargs):
        if event == self.EVENT:
            self.n += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


class GcPauses:
    """Records the collections of Python's garbage collector while active,
    as (start, seconds, generation) on the perf_counter clock."""

    def __init__(self):
        self.pauses = []
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((self._t, time.perf_counter() - self._t,
                                info["generation"]))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def describe(self) -> str:
        if not self.pauses:
            return "none"
        t, d, g = max(self.pauses, key=lambda p: p[1])
        total = 1e3 * sum(p[1] for p in self.pauses)
        return (f"{len(self.pauses)}, {total:.3f} ms in all, the longest "
                f"{1e3 * d:.3f} ms (generation {g})")


def describe_steps(run) -> str:
    """The window's steps by kind (count, median and longest ms) and the
    longest host gap between two steps: where a slow window lost time."""
    parts = []
    for kind in ("prefill", "decode"):
        ms = [1e3 * (s.end - s.start) for s in run.window_steps(kind)]
        if ms:
            parts.append(f"{kind} {len(ms)}, median {np.median(ms):.3f} "
                         f"ms, longest {max(ms):.3f} ms")
    inside = [s for s in run.steps if run.w0 <= s.end <= run.w1]
    gaps = [1e3 * (b.start - a.end) for a, b in zip(inside, inside[1:])]
    if gaps:
        parts.append(f"longest gap between steps {max(gaps):.3f} ms")
    return "; ".join(parts)


# -- the cell's files ---------------------------------------------------

def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json"):
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in {bench_path.name}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    return bench, cell, config, load_mix(cell["traffic"])


def cell_metrics(bench: dict, cell: dict, per_layer: bool) -> list:
    group = bench["per_layer" if per_layer else "end_to_end"]
    return [m for m in group if cell["name"] in m.get("workloads",
                                                      [cell["name"]])]


def reader(metric: str):
    """`read` of `metrics/<metric>.py`."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}",
        METRICS_DIR / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


LIMITS_DIR = HERE / "limits"
METRICS_DIR = HERE / "metrics"


def load_limits(cell: dict) -> dict:
    return json.loads((LIMITS_DIR / f"{cell['name']}.json").read_text())


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table or kind == "source":
        raise NoChip(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


# ModelConfig and MoEConfig fields the published keys give
PUBLISHED = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
             "num_heads": "num_attention_heads",
             "num_kv_heads": "num_key_value_heads",
             "d_ff": "intermediate_size", "vocab_size": "vocab_size",
             "head_dim": "head_dim", "rope_theta": "rope_theta",
             "tie_embeddings": "tie_word_embeddings"}
PUBLISHED_MOE = {"num_experts": "num_local_experts",
                 "top_k": "num_experts_per_tok",
                 "expert_ff": "intermediate_size"}


def model_config(cj: dict):
    """The program's `ModelConfig` for a configuration file: the sizes its
    published keys give, and each key of its `program` block, which names
    any other field of `ModelConfig`, or of `MoEConfig` bare or as
    `moe_<field>`; a group (`ssm`, `xlstm`) is an object of its fields.
    A key that names no such field, or one the published keys give,
    raises."""
    from repro.configs import base
    groups = {"ssm": base.SSMConfig, "xlstm": base.XLSTMConfig}
    top = {f.name for f in dataclasses.fields(base.ModelConfig)} - {
        "name", "moe"}
    moe_fields = {f.name for f in dataclasses.fields(base.MoEConfig)}
    kw = {f: cj[k] for f, k in PUBLISHED.items() if k in cj}
    kw["rope_theta"] = float(kw["rope_theta"])
    kw["tie_embeddings"] = bool(kw["tie_embeddings"])
    moe = ({f: cj[k] for f, k in PUBLISHED_MOE.items()}
           if cj.get("num_local_experts") else None)
    for key, value in cj.get("program", {}).items():
        bare = key[len("moe_"):] if key.startswith("moe_") else key
        if key in top and key not in kw:
            kw[key] = (groups[key](**value) if key in groups else value)
        elif bare in moe_fields and moe is not None and bare not in moe:
            moe[bare] = value
        else:
            raise ValueError(
                f"{cj['name']}: program key {key!r} names no field of "
                f"ModelConfig or MoEConfig that the published keys leave "
                f"open")
    return base.ModelConfig(
        name=cj["name"], moe=moe and base.MoEConfig(**moe), **kw)


def weight_seed(seed: int) -> int:
    """The seed as `jax.random.PRNGKey` takes it (two 32-bit words)."""
    return seed % 2**64


# -- serving --------------------------------------------------------------

def build(cj: dict, seed: int, params=None, qcfg=None):
    """The engine `repro.launch.serve.build_engine` makes for the real
    tier (a `RealExecutor` with a pool of every slot's pages and the trash
    page, `EngineConfig` defaults otherwise), serving the benchmark's
    weights (`chipbench/arch.py`) in place of the program's."""
    from repro.launch.serve import PAGE_SIZE
    from repro.serving import Engine, EngineConfig, RealExecutor
    e = cj["engine"]
    mb, mp = e["max_batch"], e["max_pages_per_seq"]
    npg = mb * mp + 1
    if params is None:
        params = arch.resolve(cj).program_params(cj, weight_seed(seed))
    ex = RealExecutor(model_config(cj), params, num_pages=npg,
                      page_size=PAGE_SIZE, max_batch=mb, qcfg=qcfg)
    return Engine(EngineConfig(max_batch=mb, page_size=PAGE_SIZE,
                               num_pages=npg, max_pages_per_seq=mp), ex)


def warm_up(eng, buckets):
    """Call every prefill length the mix can form and the decode step, on
    empty masks (their writes land on the trash page), and a slot reset."""
    import jax
    ex, B = eng.ex, eng.cfg.max_batch
    bt = eng.pm.block_tables
    for lp in buckets:
        ex.prefill(np.zeros((B, lp), np.int32), np.zeros(B, np.int32),
                   np.zeros(B, bool), bt)
    ex.decode(np.zeros(B, np.int32), np.zeros(B, bool), bt)
    ex.reset_slot(0)
    jax.block_until_ready(ex.seq_lens)


@dataclasses.dataclass
class Served:
    run: Run
    requests: list
    lateness: list
    compiles: int
    meter_usd_per_mtok: float
    gc_pauses: str                  # the window's garbage collections


def serve(cell: dict, config: dict, mix: dict, peaks: dict, *, seed: int,
          seconds: float, trace: bool, make_engine=build, fault=None,
          log=print) -> Served:
    """Set up, serve the window, and return what it recorded. `fault`
    wraps the program's executor (tests and the control only)."""
    import jax
    from repro.core import CostMeter
    eng = make_engine(config, seed)
    B = eng.cfg.max_batch
    traffic = make_traffic(mix, vocab=config["vocab_size"], max_batch=B,
                           seconds=seconds, seed=seed)
    reqs = traffic.requests
    log(f"traffic {cell['traffic']}: {len(reqs)} requests, prompts "
        f"{min(r.prompt_len for r in reqs)}-{max(r.prompt_len for r in reqs)}"
        f", outputs {min(r.max_new_tokens for r in reqs)}-"
        f"{max(r.max_new_tokens for r in reqs)}, share clipped to "
        f"{mix['max_total']} tokens {traffic.clipped_share:.4f}")
    jax.block_until_ready(eng.ex.params)
    warm_up(eng, traffic.buckets)
    if fault is not None:
        eng.ex = fault(eng.ex)
    clock = WallClock(eng, spans=trace)
    eng.ex = clock
    meter = CostMeter(peaks["usd_per_chip_hour"],
                      scrape=lambda: eng.metrics.render())

    clock.start()
    if traffic.warm_s > 0:
        eng.run(reqs, horizon=traffic.warm_s)
        w0 = traffic.warm_s
    else:
        while len(eng.slot_req) < B:
            eng.run(reqs, horizon=eng.t + 1e-3)
        w0 = clock.now()
    w1 = w0 + seconds
    setup_s = (clock.t0 + w0) - T_START

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    meter.tick()
    with CompileCounter() as compiles, GcPauses() as gc_pauses:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # the benchmark's spans suffice
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_lo = clock.now()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            eng.run(reqs, horizon=w1)
        t_hi = clock.now()
        if trace:
            jax.profiler.stop_trace()
    meter.tick()
    stats = jax.devices()[0].memory_stats() or {}

    if traffic.warm_s > 0:      # first tokens of requests due in the window
        def waiting():
            return [r for r in reqs if w0 <= r.arrival_time <= w1
                    and r.first_token_time is None]
        while waiting() and clock.now() < w1 + DRAIN_S:
            eng.run(reqs, horizon=eng.t + 0.5)

    reduced = None
    if trace:
        events = trace_reduce.read_xplane(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        reduced = trace_reduce.reduce(events)

    run = Run(cell=cell, config=config, mix=mix, peaks=peaks,
              seconds=seconds, setup_s=setup_s, w0=w0, w1=w1,
              steps=clock.steps,
              arrival={r.rid: r.arrival_time for r in reqs},
              tokens=clock.tokens, times=clock.times,
              memory_peak_bytes=stats.get("peak_bytes_in_use"),
              trace=reduced, traced=(t_lo, t_hi))
    summary = meter.summary()
    free(eng, clock)
    return Served(run, reqs, clock.lateness, compiles.n,
                  summary["time_weighted_avg"], gc_pauses.describe())


def free(eng, clock):
    """Drop the program's device state before the reference runs."""
    import jax
    ex = clock.ex
    while hasattr(ex, "ex"):        # through any wrapper to the executor
        ex = ex.ex
    for leaf in jax.tree.leaves((ex.params, ex.pools, ex.seq_lens)):
        leaf.delete()
    eng.ex = clock.ex = None
    gc.collect()


# -- correctness -------------------------------------------------------

def sample(served: Served, seed: int):
    """Requests the window finished, drawn from the seed: the one with the
    most served tokens first, then others until SAMPLE_TOKENS and
    SAMPLE_MIN requests."""
    run = served.run
    done = [r for r in served.requests if r.finish_time is not None
            and run.w0 <= r.finish_time <= run.w1 and r.rid in run.tokens]
    if not done:
        return []
    done.sort(key=lambda r: (-len(run.tokens[r.rid]), r.rid))
    rest = done[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    picked, n = [done[0]], len(run.tokens[done[0].rid])
    for i in order:
        if (n >= SAMPLE_TOKENS and len(picked) >= SAMPLE_MIN
                or len(picked) >= SAMPLE_MAX):
            break
        picked.append(rest[i])
        n += len(run.tokens[rest[i].rid])
    return picked


GAP_STATS = {             # statistics of the served tokens' logit gaps
    "mean_logit_gap": lambda g: float(np.mean(g)),
    "max_logit_gap": lambda g: float(np.max(g)),
    "p99_logit_gap": lambda g: float(np.quantile(g, 0.99)),
    "flipped_share": lambda g: float(np.mean(g > 0)),
}


def check(served: Served, config: dict, limits: dict, seed: int,
          log=print) -> tuple:
    """(the numbers `correct` compares, each with its limit: the gap
    statistics the cell's limits file names and three exact checks;
    every gap statistic)."""
    run = served.run
    picked = sample(served, seed)
    vocab = config["vocab_size"]
    in_vocab = all(0 <= t < vocab for ts in run.tokens.values() for t in ts)
    served_ok = all(len(run.tokens[r.rid]) == r.max_new_tokens
                    for r in picked)
    t0 = time.perf_counter()
    gaps = (arch.resolve(config).served_gaps(
        config, weight_seed(seed),
        [list(r.prompt) + run.tokens[r.rid] for r in picked],
        [r.prompt_len for r in picked], length=run.mix["max_total"])
        if picked else np.array([np.inf]))
    stats = {k: f(gaps) for k, f in GAP_STATS.items()}
    log(f"reference: {len(picked)} requests, {len(gaps)} served tokens "
        f"checked in {time.perf_counter() - t0:.3f}s; "
        + ", ".join(f"{k} {v}" for k, v in stats.items()))
    out = {k: {"value": stats[k], "limit": lim["limit"],
               "ok": bool(stats[k] <= lim["limit"])}
           for k, lim in limits.items()}
    out.update(
        sampled_requests={"value": len(picked), "limit": 1,
                          "ok": len(picked) >= 1},
        tokens_served_as_asked={"value": int(served_ok), "limit": 1,
                                "ok": served_ok},
        tokens_in_vocab={"value": int(in_vocab), "limit": 1, "ok": in_vocab})
    return out, stats


# -- the result line -----------------------------------------------------

def result(served: Served, bench: dict, checks: dict, device: dict,
           per_layer: bool) -> dict:
    run = served.run
    metrics = {}
    for m in cell_metrics(bench, run.cell, per_layer):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if run.open_loop:
        attempted = run.due_in_window()
        failed = sum(1 for rid in attempted if rid not in run.tokens)
    else:
        attempted = [rid for rid, ts in run.times.items()
                     if any(run.w0 <= t <= run.w1 for t in ts)]
        failed = 0
    out = {"correct": all(c["ok"] for c in checks.values()),
           "attempted": len(attempted), "failed": failed,
           "metrics": metrics, "device": device}
    if per_layer and run.trace is not None:
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    return out


def measure(cell_name: str, seed: int, seconds: float, trace: bool, *,
            bench_path: Path = ROOT / "BENCHMARK.json", devices=None,
            log=print) -> dict:
    """One run of one cell: the result line as a dict. Raises NoChip
    unless the first device is a TPU and there are enough of them."""
    bench, cell, config, mix = load_cell(cell_name, bench_path)
    limits = load_limits(cell)
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = devices or jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's first device is "
                     f"{devs[0].platform!r}")
    if len(devs) < cell["chips"]:
        raise NoChip(f"the cell asks for {cell['chips']} chips; JAX finds "
                     f"{len(devs)}")
    peaks = peaks_for(devs[0].device_kind)
    log(f"device {devs[0].platform} {devs[0].device_kind!r} x{len(devs)}; "
        f"compile cache {cache}")
    served = serve(cell, config, mix, peaks, seed=seed, seconds=seconds,
                   trace=trace, log=log)
    run = served.run
    late = served.lateness
    log(f"generator lateness: {len(late)} sleeps, overshoot p50 "
        f"{1e3 * float(np.median(late)) if late else 0.0:.3f} ms, max "
        f"{1e3 * max(late) if late else 0.0:.3f} ms")
    log(f"compilations inside the window: {served.compiles}")
    log(f"garbage collections inside the window: {served.gc_pauses}")
    log(f"window steps: {describe_steps(run)}")
    if not trace:
        log(f"CostMeter over the window: {served.meter_usd_per_mtok} $/Mtok "
            f"(engine clock, to the end of the last step); benchmark "
            f"usd_per_mtok {reader('usd_per_mtok')(run)}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    if trace:
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        for name, secs in sorted(run.trace.module_s.items()):
            log(f"trace: {name} {secs:.3f} s of device time in "
                f"{run.trace.module_n[name]} calls")
    checks, _ = check(served, config, limits, seed, log=log)
    out = result(served, bench, checks, device, trace)
    for k, c in out["checks"].items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        out = measure(args.workload, args.seed, args.seconds,
                      bool(args.trace), log=log)
    except NoChip as e:
        log(f"chipbench: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
