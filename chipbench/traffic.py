"""The one traffic generator: a mix file of parameters in, requests out.

A mix (`chipbench/traffic/<name>.json`) states its `kind` and its
parameters:

- `backlog`: every request is due at time 0; the count is
  `requests.per_slot * max_batch + requests.per_second * seconds`, enough
  that the queue never empties during the window.
- `poisson`: open-loop arrivals at `rate_per_s`, from time 0 to
  `warm_s + seconds` (the window opens at `warm_s`): the
  `rate_per_s * (warm_s + seconds)` gaps are the quantiles of the
  exponential, scaled to fill that span exactly, in a fixed shuffled
  order.

Lengths are lognormal (`median`, `sigma`), clipped to `[min, max]`, the
output further clipped so that prompt + output <= `max_total`.

Every seed gets the same work: the seed draws the prompt token ids, over
the whole vocabulary, and nothing else. The lengths are the quantiles of
their distributions on a fixed grid, dealt into blocks of about
`max_batch` requests that each span both distributions, paired and
ordered within each block by fixed permutations. (The served model's
cost does not depend on token values, so runs on different seeds do the
same work; when the seed also shuffled the order, the window of a
saturated cell saw different completions and prefills, and its $/M-tok
spread 7-8% across seeds.)
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List, Tuple

import numpy as np

from repro.serving.request import Request

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


@dataclasses.dataclass
class Traffic:
    requests: List[Request]
    warm_s: float               # engine time at which an open loop's window opens
    clipped_share: float        # share of requests whose output was cut to max_total
    buckets: Tuple[int, ...]    # prefill lengths (multiples of 64) the mix can produce


def _quantiles(median: float, sigma: float, n: int) -> np.ndarray:
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return median * np.exp(sigma * z)


def lengths(mix: dict, n: int, block: int
            ) -> Tuple[List[np.ndarray], List[np.ndarray], float]:
    """(prompt lengths, output lengths, clipped share), in blocks of about
    `block` pairs: the same blocks for every seed. Block k takes every
    `n_blocks`-th quantile of each distribution from the k-th on, so each
    block spans both distributions; the pairing is a fixed permutation."""
    p, o, cap = mix["prompt"], mix["output"], mix["max_total"]
    prompts = np.clip(np.rint(_quantiles(p["median"], p["sigma"], n)),
                      p["min"], p["max"]).astype(np.int64)
    outs = np.clip(np.rint(_quantiles(o["median"], o["sigma"], n)),
                   o["min"], o["max"]).astype(np.int64)
    nb = -(-n // block)
    fixed = np.random.default_rng(0)
    pb, ob, n_clipped = [], [], 0
    for k in range(nb):
        pk = prompts[k::nb]
        ok = outs[k::nb][fixed.permutation(len(pk))]
        room = mix["max_total"] - pk
        n_clipped += int(np.sum(ok > room))
        pb.append(pk)
        ob.append(np.minimum(ok, room))
    return pb, ob, n_clipped / n


def prefill_buckets(mix: dict) -> Tuple[int, ...]:
    """Padded prefill lengths the engine can form from this mix's prompts
    (`Engine._prefill_tokens` pads to a multiple of 64)."""
    lo = -(-mix["prompt"]["min"] // 64) * 64
    hi = -(-mix["prompt"]["max"] // 64) * 64
    return tuple(range(lo, hi + 1, 64))


def request_count(mix: dict, max_batch: int, seconds: float) -> int:
    if mix["kind"] == "backlog":
        r = mix["requests"]
        return int(r["per_slot"] * max_batch
                   + math.ceil(r["per_second"] * seconds))
    if mix["kind"] == "poisson":
        return max(1, round(mix["rate_per_s"] * (mix["warm_s"] + seconds)))
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")


def make_traffic(mix: dict, *, vocab: int, max_batch: int, seconds: float,
                 seed: int) -> Traffic:
    n = request_count(mix, max_batch, seconds)
    pb, ob, clipped = lengths(mix, n, max_batch)
    fixed = np.random.default_rng(1)
    order = [fixed.permutation(len(pk)) for pk in pb]
    prompts = np.concatenate([pk[o] for pk, o in zip(pb, order)])
    outs = np.concatenate([ok[o] for ok, o in zip(ob, order)])
    if mix["kind"] == "backlog":
        arrivals = np.zeros(n)
        warm_s = 0.0
    else:
        warm_s = float(mix["warm_s"])
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps *= (warm_s + seconds) / gaps.sum()
        arrivals = np.cumsum(gaps[fixed.permutation(n)])
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, arrival_time=float(a), prompt_len=int(p),
                    max_new_tokens=int(o),
                    prompt=rng.integers(0, vocab, int(p)).tolist())
            for i, (a, p, o) in enumerate(zip(arrivals, prompts, outs))]
    return Traffic(reqs, warm_s, clipped, prefill_buckets(mix))
