"""Serving driver: engine + live cost meter under an offered-load schedule.

    PYTHONPATH=src python -m repro.launch.serve --arch llama31-8b \
        --tier sim --hw tpu-v5e --lam 5 --requests 200
    PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-1b-a400m \
        --tier real --lam 4 --requests 32

real tier: the full config on the local device (seeded random weights),
           wall-clock JAX execution, priced by the device it ran on.
sim tier:  full config on the calibrated TPU step-time model (`--hw`).
The meter scrapes the engine's Prometheus text every 10 engine-clock
seconds and prints the live $/M-tok — the vllm-cost-meter analogue.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core import CostMeter, chip_hour_price
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_params
from repro.serving import (ArrivalSpec, Engine, EngineConfig, RealExecutor,
                           SimExecutor, synth_requests)
from repro.simulate import HW_BY_NAME, StepTimeModel, hw_for_device_kind

# slots per tier. The pool holds every slot's longest request plus the
# trash page. Live: granite-moe-1b-a400m's bf16 KV pool (32 x 64 + 1
# pages x 16 tokens x 24 layers = 1.6 GB) and its 2.7 GB of weights fit
# one 16 GB v5e with the step's undonated pool copy. A model whose
# weights alone fill the device (llama31-8b) does not fit the real tier.
TIER_MAX_BATCH = {"real": 32, "sim": 256}
PAGE_SIZE = 16          # tokens per KV page


def build_engine(cfg: ModelConfig, tier: str, hw: str = "tpu-v5e",
                 quant: str = "bf16", n_chips: int = 1,
                 max_batch: int | None = None, max_pages_per_seq: int = 64,
                 seed: int = 0) -> Engine:
    """An engine serving `cfg` on the real tier (this process's first
    device) or the sim tier (`hw` x `n_chips` step-time model), with a
    KV pool of `max_batch * max_pages_per_seq` pages plus the trash
    page: enough for every slot, and no page that can never be used."""
    mb = max_batch or TIER_MAX_BATCH[tier]
    npg = mb * max_pages_per_seq + 1
    if tier == "real":
        if quant != "bf16" or n_chips != 1:
            raise ValueError("the real tier serves bf16 on one device; got "
                             f"quant={quant!r}, n_chips={n_chips}")
        params = init_params(jax.random.PRNGKey(seed), cfg)
        ex = RealExecutor(cfg, params, num_pages=npg, page_size=PAGE_SIZE,
                          max_batch=mb)
    else:
        stm = StepTimeModel(cfg, HW_BY_NAME[hw], n_chips=n_chips,
                            quant=quant)
        ex = SimExecutor(cfg, stm)
    ecfg = EngineConfig(max_batch=mb, page_size=PAGE_SIZE, num_pages=npg,
                        max_pages_per_seq=max_pages_per_seq)
    return Engine(ecfg, ex)


def tier_price(tier: str, hw: str = "tpu-v5e", n_chips: int = 1) -> float:
    """$/hr the meter charges: the sim tier's `hw` x `n_chips`, or the
    device the real tier runs on (an unknown device kind raises)."""
    if tier == "real":
        return hw_for_device_kind(
            jax.devices()[0].device_kind).price_per_chip_hr
    return chip_hour_price(hw, n_chips)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama31-8b")
    ap.add_argument("--tier", default="sim", choices=["real", "sim"])
    ap.add_argument("--hw", default="tpu-v5e",
                    help="sim tier only: the modelled hardware")
    ap.add_argument("--quant", default="bf16")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--lam", type=float, default=5.0)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--io-shape", default="chat")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compare-tier", default=None)
    ap.add_argument("--accept-slo-mismatch", action="store_true")
    args = ap.parse_args(argv)
    use_compile_cache()

    price = tier_price(args.tier, args.hw, args.chips)   # before the build
    eng = build_engine(get_config(args.arch), args.tier, args.hw, args.quant,
                       args.chips, seed=args.seed)
    meter = CostMeter(price, scrape=lambda: eng.metrics.render())

    spec = ArrivalSpec(lam=args.lam, n_requests=args.requests,
                       io_shape=args.io_shape, scale=args.scale,
                       seed=args.seed)
    reqs = synth_requests(spec)

    # drive the engine in slices so the meter ticks mid-run
    horizon = 0.0
    meter.tick()
    while any(r.finish_time is None and r.retries <= 2 for r in reqs):
        horizon += 10.0
        eng.run(reqs, horizon=horizon)
        s = meter.tick()
        if s:
            print(f"[meter t={s.t:8.1f}s] tps={s.tps:9.1f} "
                  f"inflight={s.inflight:5.0f} $/MTok={s.c_eff:10.4f}")
        if horizon > 24 * 3600:
            break

    summ = meter.summary()
    print(f"\nmeter summary: best-minute=${summ['best_minute']:.4f} "
          f"worst-minute=${summ['worst_minute']:.4f} "
          f"avg=${summ['time_weighted_avg']:.4f}")
    done = [r for r in reqs if r.finish_time is not None]
    if done:
        print(f"completed {len(done)}/{len(reqs)}  "
              f"TTFT p50={1e3*float(np.median([r.ttft for r in done])):.1f}ms")
    if args.compare_tier:
        print(meter.compare_api(
            args.compare_tier,
            accept_slo_mismatch=args.accept_slo_mismatch))


if __name__ == "__main__":
    main()
