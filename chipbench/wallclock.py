"""Put the engine on the wall clock, and record what every step served.

`Engine` advances its clock by whatever duration the executor returns
and jumps idle gaps at once. `WallClock` wraps the engine's executor so
that the engine's clock is the wall clock since `start()`:

- before a step it sleeps until the wall clock reaches `eng.t`, so an
  idle jump becomes a real wait for the next arrival;
- it returns `dt` = wall time at the step's end minus the engine time
  already accounted for (`eng.t`, or the end of the step it last
  reported), so host scheduling time between steps falls inside the
  next step's `dt`. Several steps of one `decode_multi` call (the
  executor's own, which calls `decode` once a step) add up to the wall
  time they took.

A request is timed from when it was due: an arrival in the middle of a
step waits for that step. The wrapper also records, per step, its kind,
start and end, which request each row served and the tokens it emitted.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from repro.serving.executors import RealExecutor

try:
    from jax.profiler import TraceAnnotation
except ImportError:             # pragma: no cover
    TraceAnnotation = None


class _NoSpan:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@dataclasses.dataclass
class Step:
    kind: str                   # "prefill" | "decode"
    start: float                # wall seconds since start(), after any sleep
    end: float
    rids: List[int]             # requests served by this step, row order
    ctx: List[int]              # each row's context after the step (tokens)
    new_tokens: List[int]       # prompt tokens (prefill) or 1 each (decode)


class WallClock:
    """Executor wrapper; `eng.ex = WallClock(eng)` after the warm-up."""

    def __init__(self, eng, spans: bool = True):
        self.eng = eng
        self.ex = eng.ex
        self.needs_tokens = getattr(self.ex, "needs_tokens", True)
        self.t0: Optional[float] = None
        self.steps: List[Step] = []
        self.tokens: Dict[int, List[int]] = {}     # rid -> served token ids
        self.times: Dict[int, List[float]] = {}    # rid -> emission times
        self.lateness: List[float] = []            # sleep overshoots (s)
        self._told = 0.0            # end of the last step reported
        self._span = TraceAnnotation if (spans and TraceAnnotation) else _NoSpan

    # -- clock ----------------------------------------------------------
    def start(self):
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _wait(self):
        """Sleep until the wall clock reaches the engine's clock."""
        target = self.eng.t
        ahead = target - self.now()
        if ahead > 0:
            with self._span("chipbench.sleep"):
                time.sleep(ahead)
            self.lateness.append(self.now() - target)

    def _dt(self, end: float) -> float:
        """Engine time a step that ended at `end` takes."""
        dt = end - max(self.eng.t, self._told)
        self._told = end
        return dt

    # -- executor interface ---------------------------------------------
    def reset_slot(self, slot: int):
        self.ex.reset_slot(slot)

    def prefill(self, tokens, lens, do_mask, block_tables):
        self._wait()
        start = self.now()
        slots = np.flatnonzero(do_mask)
        rids = [self.eng.slot_req[int(s)].rid for s in slots]
        with self._span("executor.prefill"):
            first, _ = self.ex.prefill(tokens, lens, do_mask, block_tables)
        end = self.now()
        for s, rid in zip(slots, rids):
            self.tokens[rid] = [int(first[s])]
            self.times[rid] = [end]
        n = [int(lens[s]) for s in slots]
        self.steps.append(Step("prefill", start, end, rids, n, n))
        return first, self._dt(end)

    def decode(self, tokens, active, block_tables, context_lens=None):
        self._wait()
        start = self.now()
        slots = np.flatnonzero(active)
        reqs = [self.eng.slot_req[int(s)] for s in slots]
        rids = [r.rid for r in reqs]
        with self._span("executor.decode"):
            nxt, _ = self.ex.decode(tokens, active, block_tables)
        end = self.now()
        ctx = []
        for s, r in zip(slots, reqs):
            self.tokens[r.rid].append(int(nxt[s]))
            self.times[r.rid].append(end)
            # keys this step attended over: the prompt and every token
            # fed back so far, this step's input included
            ctx.append(r.prompt_len + len(self.tokens[r.rid]) - 1)
        self.steps.append(Step("decode", start, end, rids, ctx,
                               [1] * len(rids)))
        return nxt, self._dt(end)

    decode_multi = RealExecutor.decode_multi
