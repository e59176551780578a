"""Bring-up smoke: the live tier on one TPU at granite-moe-1b-a400m's
published widths, then the compiled fleet simulator on the same chip.

    python chip_smoke.py

Phase 1 (live tier). `launch.serve.build_engine` builds the full
granite-moe-1b-a400m config from seeded random weights. The prefill and
decode shapes compile before the window. The engine then serves seeded
Poisson chat requests (512:256) to completion with the cost meter priced
by the device it runs on. Afterwards, at the model's widths, the Pallas
paged-attention kernel is checked against `paged_attention_ref` on the
same pools, and one teacher-forced decode step's logits through the
kernel are checked against the reference path. Last, the decode step
built with the kernel and with the reference, and one layer's paged
attention alone, are timed at the served requests' mean context (a
reading, not a check).

Phase 2 (sim tier on the device). A small `paper_atlas` slice runs with
`backend="jit"` in this process. Every lane must take the compiled loop,
and its records must match the numpy fleet within
`precision.jit_tolerance()` under float64. It runs second because
enabling x64 is process-global.

Exits non-zero, printing no result, unless JAX's first device is a TPU.
The last line of stdout is {"ok": true, "device": {...}}.
"""
from __future__ import annotations

import functools
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

ARCH = "granite-moe-1b-a400m"
# paged kernel vs reference on the same bf16 pools: the bf16 bound the
# kernel tests use (tests/test_kernels.py)
KERNEL_ATOL = 0.06
# one teacher-forced decode step, kernel vs reference path: largest logit
# difference over the largest reference logit. The two paths round
# attention differently (f32 in the kernel, bf16 scores and weights in
# the reference) and the difference passes through every layer.
LOGIT_RTOL = 0.05
# the atlas slice phase 2 runs: three models x three offered rates on v5e
SIM_LAMS = (1.0, 3.02, 9.09)
# timed calls per function in the decode timing, after one to compile
TIMED_STEPS = 10


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


class Failed(RuntimeError):
    """A smoke check did not hold."""


def _check(ok: bool, what: str):
    if not ok:
        raise Failed(what)


def _timed(fn, sink, tokens):
    """Wrap an executor step: record its duration and the emitted tokens."""
    def wrapped(*args, **kwargs):
        out, dt = fn(*args, **kwargs)
        sink.append(dt)
        tokens.append(out)
        return out, dt
    return wrapped


def run_live(cfg, *, price_per_hr: float, n_requests: int = 32,
             lam: float = 4.0, scale: float = 1.0,
             max_batch: int | None = None, max_pages_per_seq: int = 64,
             seed: int = 0, log=print) -> dict:
    """Serve chat requests through the live tier, check its kernel, and
    time the decode step on both attention paths.

    `max_batch` defaults to the live tier's (`launch.serve.
    TIER_MAX_BATCH`); the served path picks its kernel by platform, and
    the checks call the Pallas kernel explicitly."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import CostMeter
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref
    from repro.launch.serve import build_engine
    from repro.serving import ArrivalSpec, synth_requests
    from repro.serving.runner import init_pools, make_decode_logits

    t0 = time.perf_counter()
    eng = build_engine(cfg, "real", max_batch=max_batch,
                       max_pages_per_seq=max_pages_per_seq, seed=seed)
    ex, B, page_size = eng.ex, eng.cfg.max_batch, eng.cfg.page_size
    jax.block_until_ready(ex.params)
    log(f"build: {cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} vocab={cfg.vocab_size} "
        f"max_batch={B} pages={eng.cfg.num_pages}x{page_size} "
        f"in {time.perf_counter() - t0:.2f}s")

    reqs = synth_requests(ArrivalSpec(lam=lam, n_requests=n_requests,
                                      io_shape="chat", scale=scale,
                                      seed=seed))

    # -- compile every shape the window uses ----------------------------
    t0 = time.perf_counter()
    bt = eng.pm.block_tables
    for lp in sorted({-(-r.prompt_len // 64) * 64 for r in reqs}):
        ex.prefill(np.zeros((B, lp), np.int32), np.zeros(B, np.int32),
                   np.zeros(B, bool), bt)
    ex.decode(np.zeros(B, np.int32), np.zeros(B, bool), bt)
    ex.reset_slot(0)
    jax.block_until_ready(ex.seq_lens)
    compile_s = time.perf_counter() - t0
    log(f"compile (prefill + decode shapes, before the window): "
        f"{compile_s:.2f}s")

    # -- the served window ----------------------------------------------
    prefill_s, decode_s, emitted = [], [], []
    ex.prefill = _timed(ex.prefill, prefill_s, emitted)
    ex.decode = _timed(ex.decode, decode_s, emitted)
    meter = CostMeter(price_per_hr, scrape=lambda: eng.metrics.render())
    meter.tick()
    t0 = time.perf_counter()
    with CompileCounter() as compiles:
        horizon = 0.0
        while any(r.finish_time is None for r in reqs):
            horizon += 5.0
            eng.run(reqs, horizon=horizon)
            meter.tick()
            _check(horizon < 24 * 3600, "engine clock ran past a day")
    window_s = time.perf_counter() - t0

    done = [r for r in reqs if r.finish_time is not None]
    gen = int(eng.metrics.get("repro:generation_tokens_total"))
    tok_min = min(int(t.min()) for t in emitted)
    tok_max = max(int(t.max()) for t in emitted)
    ttft_p50 = float(np.median([r.ttft for r in done]))
    c_eff = meter.summary()["time_weighted_avg"]
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"served: {len(done)}/{len(reqs)} requests, {gen} generated tokens, "
        f"wall window {window_s:.3f}s, engine clock {eng.t:.3f}s")
    log(f"compilations inside the window: {compiles.n}")
    log(f"prefill step median: {1e3 * float(np.median(prefill_s)):.3f}ms "
        f"({len(prefill_s)} steps)")
    log(f"decode step median: {1e3 * float(np.median(decode_s)):.3f}ms "
        f"({len(decode_s)} steps)")
    log(f"TTFT p50: {1e3 * ttft_p50:.3f}ms")
    log(f"tokens/s: {gen / window_s:.1f} (wall window), "
        f"{gen / eng.t:.1f} (engine clock)")
    log(f"metered $/M-tok: {c_eff:.4f} at ${price_per_hr}/hr")
    log(f"peak_bytes_in_use: {peak if peak is not None else 'not reported'}")
    _check(len(done) == len(reqs), "not every request finished")
    _check(gen == sum(r.max_new_tokens for r in reqs),
           "generated tokens do not add up to the requested outputs")
    _check(0 <= tok_min and tok_max < cfg.vocab_size,
           f"token ids outside the vocabulary: [{tok_min}, {tok_max}]")
    _check(compiles.n == 0, f"{compiles.n} compilations inside the window")
    _check(math.isfinite(c_eff) and c_eff > 0, f"metered $/M-tok {c_eff}")

    # -- did the served decode step carry the Pallas kernel? ------------
    hlo = ex.decode_fn.lower(
        ex.params, ex.pools, jnp.asarray(bt), ex.seq_lens,
        jnp.zeros(B, jnp.int32), jnp.zeros(B, bool)).as_text()
    decode_kernel = "tpu_custom_call" in hlo
    log(f"served decode step carries the Pallas paged kernel: "
        f"{decode_kernel}")

    # -- kernel checks at the model's widths, on small pools ------------
    rng = np.random.default_rng(seed)
    nb, L = 4, 64                       # rows, teacher-forced prompt length
    npp = -(-(L + 1) // page_size)
    pools = init_pools(cfg, 1 + nb * npp, page_size, nb)
    tbl = np.zeros((nb, max_pages_per_seq), np.int32)
    tbl[:, :npp] = 1 + np.arange(nb * npp).reshape(nb, npp)
    tbl = jnp.asarray(tbl)
    seq = rng.integers(0, cfg.vocab_size, (nb, L + 1)).astype(np.int32)
    lens = jnp.full((nb,), L, jnp.int32)
    _, pools, sl = ex.prefill_fn(ex.params, pools, tbl,
                                 jnp.zeros(nb, jnp.int32),
                                 jnp.asarray(seq[:, :L]), lens,
                                 jnp.ones(nb, bool))

    hd = cfg.resolved_head_dim
    kp, vp = pools[0]["k"][0], pools[0]["v"][0]       # layer 0
    q = jnp.asarray(rng.normal(size=(nb, cfg.num_heads, hd)), jnp.bfloat16)
    out_k = paged_attention(q, kp, vp, tbl, sl, use_kernel=True)
    out_r = paged_attention_ref(q, kp, vp, tbl, sl)
    kerr = float(jnp.max(jnp.abs(out_k.astype(jnp.float32)
                                 - out_r.astype(jnp.float32))))
    log(f"paged kernel vs paged_attention_ref (layer-0 pools, "
        f"{cfg.num_heads}/{cfg.num_kv_heads} heads, D={hd}): "
        f"max|diff|={kerr:.6f} (atol {KERNEL_ATOL})")
    _check(kerr <= KERNEL_ATOL, "paged kernel disagrees with the reference")

    args = (ex.params, pools, tbl, sl, jnp.asarray(seq[:, L]),
            jnp.ones(nb, bool))
    lk = jax.jit(make_decode_logits(cfg, page_size, use_kernel=True))(
        *args)[0]
    lr = jax.jit(make_decode_logits(cfg, page_size, use_kernel=False))(
        *args)[0]
    lerr = float(jnp.max(jnp.abs(lk - lr)) / jnp.max(jnp.abs(lr)))
    agree = float(jnp.mean(jnp.argmax(lk, -1) == jnp.argmax(lr, -1)))
    log(f"teacher-forced decode logits, kernel vs reference path: "
        f"max|diff|/max|ref|={lerr:.6f} (rtol {LOGIT_RTOL}), "
        f"argmax agreement {agree:.2f}")
    _check(bool(jnp.all(jnp.isfinite(lk))), "non-finite kernel logits")
    _check(lerr <= LOGIT_RTOL, "decode logits disagree with the reference")

    ctx = int(np.mean([r.prompt_len + r.max_new_tokens // 2 for r in reqs]))
    timing = time_decode_paths(cfg, ex, B, max_pages_per_seq, ctx, log=log)

    return {"requests": len(done), "generated": gen, "compile_s": compile_s,
            "window_s": window_s, "compiles_in_window": compiles.n,
            "prefill_ms_p50": 1e3 * float(np.median(prefill_s)),
            "decode_ms_p50": 1e3 * float(np.median(decode_s)),
            "ttft_ms_p50": 1e3 * ttft_p50, "tokens_per_s": gen / window_s,
            "c_eff": c_eff, "peak_bytes_in_use": peak,
            "decode_kernel": decode_kernel, "kernel_err": kerr,
            "logit_err": lerr, **timing}


def time_decode_paths(cfg, ex, max_batch: int, max_pages_per_seq: int,
                      ctx: int, log=print) -> dict:
    """Median host-clock time (ms) around `block_until_ready` of the
    decode step built with the Pallas kernel and with the jnp reference,
    and of one layer's paged attention alone, at `max_batch` rows of
    `ctx` tokens on the executor's weights and pools. Each function
    compiles once before it is timed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention.ops import paged_attention
    from repro.serving.runner import make_step_fns

    def median_ms(fn, *args):
        jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(TIMED_STEPS):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        return 1e3 * float(np.median(ts))

    B, page_size = max_batch, ex.page_size
    npp = -(-ctx // page_size)
    bt = np.zeros((B, max_pages_per_seq), np.int32)
    bt[:, :npp] = 1 + np.arange(B * npp).reshape(B, npp)
    bt, sl = jnp.asarray(bt), jnp.full((B,), ctx - 1, jnp.int32)
    tok, act = jnp.zeros(B, jnp.int32), jnp.ones(B, bool)
    kp, vp = ex.pools[0]["k"][0], ex.pools[0]["v"][0]       # layer 0
    q = jnp.ones((B, cfg.num_heads, cfg.resolved_head_dim), jnp.bfloat16)
    out = {}
    for path, use_kernel in (("kernel", True), ("reference", False)):
        decode = make_step_fns(cfg, page_size, use_kernel=use_kernel)[1]
        out[f"decode_ms_{path}"] = median_ms(
            decode, ex.params, ex.pools, bt, sl, tok, act)
        attn = jax.jit(functools.partial(paged_attention,
                                         use_kernel=use_kernel))
        out[f"attn_ms_{path}"] = median_ms(attn, q, kp, vp, bt, sl)
    log(f"decode step timed (B={B}, ctx {ctx}, median of {TIMED_STEPS}): "
        f"kernel {out['decode_ms_kernel']:.3f}ms, "
        f"reference {out['decode_ms_reference']:.3f}ms")
    log(f"one layer's paged attention timed: "
        f"kernel {out['attn_ms_kernel']:.3f}ms, "
        f"reference {out['attn_ms_reference']:.3f}ms")
    return out


def run_sim(lams=SIM_LAMS, log=print) -> dict:
    """A jit-eligible `paper_atlas` slice on the compiled fleet in this
    process, against the numpy fleet.

    `backend="jit"` sends a lane to the numpy fleet when the fleet cannot
    take its cell, when `jit_eligible` refuses it, or when the compiled
    loop raises `JitFallback` on its chunk. The routes are counted by
    asking those same questions of the slice's lanes, which fit one
    chunk (`runner.JIT_LANE_WIDTH`)."""
    from repro.experiments.plans import get_plan
    from repro.experiments.runner import (JIT_LANE_WIDTH, PlanRunner,
                                          _fleet_eligible, _fleet_point)
    from repro.serving import precision
    from repro.serving.arrivals import synth_arrays
    from repro.serving.fleet_jit import (JitFallback, _run_jit_fleet,
                                         jit_eligible)

    _check(precision.enable_x64(), "jax refused 64-bit mode")
    plan = get_plan("paper_atlas").subset(
        lambda c: c.hw == "tpu-v5e" and c.lam in lams)
    n = len(plan.cells)
    _check(n <= JIT_LANE_WIDTH, f"{n} lanes do not fit one jit chunk")
    oracle = PlanRunner(plan).run(parallel=False, backend="vector")
    t0 = time.perf_counter()
    got = PlanRunner(plan).run(backend="jit")
    jit_s = time.perf_counter() - t0

    points = [_fleet_point(c, None) for c in plan.cells
              if _fleet_eligible(c, None)]
    streams = [synth_arrays(p.arrivals) for p in points]
    eligible = [i for i, (p, s) in enumerate(zip(points, streams))
                if jit_eligible(p, s)]
    try:
        _run_jit_fleet([points[i] for i in eligible],
                       [streams[i] for i in eligible])
        fallback = 0
    except JitFallback:
        fallback = len(eligible)
    routes = {"compiled": len(eligible) - fallback,
              "numpy": n - len(eligible), "fallback": fallback}
    bad = precision.record_mismatches(oracle, got)
    log(f"jit fleet: {n} paper_atlas lanes in {jit_s:.3f}s (compile "
        f"included), routes {routes}")
    log(f"jit fleet vs numpy fleet: {len(bad)} field mismatches beyond "
        f"rtol/atol {precision.jit_tolerance()}")
    _check(routes == {"compiled": n, "numpy": 0, "fallback": 0},
           f"not every lane took the compiled loop: {routes}")
    _check(not bad, f"jit records disagree with numpy: {bad[:3]}")
    return {"lanes": n, "routes": routes, "jit_s": jit_s}


def main() -> int:
    cache_dir = use_compile_cache()
    import jax

    from repro.configs import get_config
    from repro.launch.serve import tier_price

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(jax.devices())}"
          f"; compile cache {cache_dir}", flush=True)
    live = run_live(get_config(ARCH), price_per_hr=tier_price("real"))
    _check(live["decode_kernel"], "the served decode step has no Pallas "
           "kernel on the TPU")
    run_sim()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
