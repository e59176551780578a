"""Execution tiers behind the engine: real JAX steps or a TPU time model.

RealExecutor — owns the device state (pools, seq_lens), runs the jitted
prefill/decode closures, returns wall-clock durations. JAX is imported
lazily so sim-only processes (parallel_sweep workers) never pay for it.

SimExecutor — same interface, zero compute: durations come from a
calibrated step-time model (repro.simulate.step_time) so the engine's
scheduler/queueing dynamics play out on a virtual TPU clock. Token values
are irrelevant to cost metering (only counts and timing matter), so it
emits zeros and advertises `needs_tokens = False` (the engine then skips
materialising prompt token matrices).

`decode_multi(tokens, active, block_tables, context_lens, max_steps,
time_budget)` is the fast-forward hook: take up to `max_steps` decode
steps with a frozen batch, stopping after the first step whose cumulative
duration reaches `time_budget` (events are processed at the top of the
engine loop, i.e. *after* the step that crosses them — identical to the
per-token reference loop). SimExecutor answers in O(log k) closed-form
model evaluations; RealExecutor falls back to per-step execution because
wall-clock durations cannot be predicted.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.serving.tracing import span


class RealExecutor:
    """Wall-clock tier: real logits, real latencies on the local device.

    `use_kernel=None` serves decode through the Pallas paged kernel on a
    TPU and through the jnp reference elsewhere."""

    needs_tokens = True

    def __init__(self, cfg, params, *, num_pages: int, page_size: int,
                 max_batch: int, qcfg=None, use_kernel: Optional[bool] = None):
        import jax
        import jax.numpy as jnp
        from repro.serving.runner import init_pools, make_step_fns
        self._jax, self._jnp = jax, jnp
        self.cfg = cfg
        self.params = params
        self.page_size = page_size
        self.pools = init_pools(cfg, num_pages, page_size, max_batch)
        self.seq_lens = jnp.zeros((max_batch,), jnp.int32)
        self.prefill_fn, self.decode_fn = make_step_fns(
            cfg, page_size, qcfg=qcfg, use_kernel=use_kernel)

    def reset_slot(self, slot: int):
        self.seq_lens = self.seq_lens.at[slot].set(0)

    def prefill(self, tokens: np.ndarray, lens: np.ndarray,
                do_mask: np.ndarray, block_tables: np.ndarray
                ) -> Tuple[np.ndarray, float]:
        import time
        jax, jnp = self._jax, self._jnp
        t0 = time.perf_counter()
        with span("executor.prefill.inputs"):
            bt, tokens, lens, do_mask = (
                jnp.asarray(block_tables), jnp.asarray(tokens),
                jnp.asarray(lens), jnp.asarray(do_mask))
        with span("executor.prefill.launch"):
            first, self.pools, self.seq_lens = self.prefill_fn(
                self.params, self.pools, bt, self.seq_lens, tokens, lens,
                do_mask)
        with span("executor.prefill.wait"):
            first = np.asarray(jax.block_until_ready(first))
        return first, time.perf_counter() - t0

    def decode(self, tokens: np.ndarray, active: np.ndarray,
               block_tables: np.ndarray) -> Tuple[np.ndarray, float]:
        import time
        jax, jnp = self._jax, self._jnp
        t0 = time.perf_counter()
        with span("executor.decode.inputs"):
            bt, tokens, active = (jnp.asarray(block_tables),
                                  jnp.asarray(tokens), jnp.asarray(active))
        with span("executor.decode.launch"):
            nxt, self.pools, self.seq_lens = self.decode_fn(
                self.params, self.pools, bt, self.seq_lens, tokens, active)
        with span("executor.decode.wait"):
            nxt = np.asarray(jax.block_until_ready(nxt))
        return nxt, time.perf_counter() - t0

    def decode_multi(self, tokens: np.ndarray, active: np.ndarray,
                     block_tables: np.ndarray, context_lens: np.ndarray,
                     max_steps: int, time_budget: Optional[float] = None
                     ) -> Tuple[np.ndarray, float, int]:
        """Per-step fallback: real logits cannot be fast-forwarded."""
        cur = np.array(tokens)
        total = 0.0
        steps = 0
        while steps < int(max_steps):
            nxt, dt = self.decode(cur, active, block_tables)
            cur[active] = nxt[active]
            total += dt
            steps += 1
            if time_budget is not None and total >= time_budget:
                break
        return cur, total, max(steps, 1)


class SimExecutor:
    """Virtual-clock tier: step durations from the TPU step-time model."""

    needs_tokens = False

    def __init__(self, cfg, step_time_model, *, page_size: int = 16):
        self.cfg = cfg
        self.model = step_time_model
        self.page_size = page_size
        self._seq_lens: Optional[np.ndarray] = None

    def reset_slot(self, slot: int):
        pass

    def prefill(self, tokens: np.ndarray, lens: np.ndarray,
                do_mask: np.ndarray, block_tables: np.ndarray
                ) -> Tuple[np.ndarray, float]:
        n_tok = int(lens[do_mask].sum())
        dt = self.model.prefill_time(n_tok, int(do_mask.sum()))
        return np.zeros(tokens.shape[0], np.int32), dt

    def decode(self, tokens: np.ndarray, active: np.ndarray,
               block_tables: np.ndarray, context_lens=None
               ) -> Tuple[np.ndarray, float]:
        bs = int(active.sum())
        ctx = (float(np.mean(context_lens[active]))
               if context_lens is not None and bs else 0.0)
        dt = self.model.decode_time(bs, ctx)
        return np.zeros(tokens.shape[0], np.int32), dt

    def decode_multi(self, tokens: np.ndarray, active: np.ndarray,
                     block_tables: np.ndarray, context_lens: np.ndarray,
                     max_steps: int, time_budget: Optional[float] = None
                     ) -> Tuple[np.ndarray, float, int]:
        """Closed-form jump: every context grows by one token per step, so
        the k-step duration is `StepTimeModel.decode_time_multi`; the step
        count crossing `time_budget` is found by bisection on that O(1)
        sum (smallest k with S(k) >= budget, capped at max_steps)."""
        bs = int(active.sum())
        ctx0 = (float(np.mean(context_lens[active]))
                if context_lens is not None and bs else 0.0)
        k = max(int(max_steps), 1)
        m = self.model
        if (time_budget is not None and k > 1 and
                m.decode_time_multi(bs, ctx0, k) >= time_budget):
            lo, hi = 1, k
            while lo < hi:
                mid = (lo + hi) // 2
                if m.decode_time_multi(bs, ctx0, mid) >= time_budget:
                    hi = mid
                else:
                    lo = mid + 1
            k = lo
        dt = m.decode_time_multi(bs, ctx0, k)
        return np.zeros(tokens.shape[0], np.int32), dt, k
