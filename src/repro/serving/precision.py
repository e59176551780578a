"""Centralized numeric-precision policy for the jit fleet backend.

The numpy fleet (`serving.fleet`) is float64 by construction — numpy has
no other default — and its RunRecords are *bitwise* commitments (the
committed stores). JAX, by contrast, defaults to float32 unless
``jax_enable_x64`` is flipped, and flipping it ad hoc from inside a
simulator module is how dtype drift starts. This module is the one
place that policy lives (ISSUE 7 satellite):

* `enable_x64()` — idempotently turn on ``jax_enable_x64`` and report
  whether 64-bit mode is actually active. Safe to call any number of
  times, safe to call before or after other jax users; the tier-1 suite
  runs the kernel/model tests under the flag to pin that enabling it
  does not perturb them.
* `active_x64()` — query without side effects (False until someone
  enabled it, or when jax is unavailable).
* `jit_tolerance()` — the documented jit-vs-numpy RunRecord agreement
  bound as ``(rtol, atol)``. Under x64 the jit fleet replays the same
  float64 op sequence as the numpy fleet; XLA:CPU may still contract
  mul+add chains into FMAs, so equality is *tolerance*-based (tight),
  not bitwise — the numpy path stays the bitwise oracle. Without x64
  (jax built without 64-bit support) the jit path runs float32 and the
  bound is correspondingly loose; the backend still works, it is just
  no longer a store-regeneration surface.

The numpy path never touches this module's jax config: `FleetStepModel`
and `FleetEngine` are pure numpy, so enabling x64 cannot move a single
bit of the committed stores (`tests/test_fleet_jit.py` pins this).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence, Tuple

# documented jit-vs-numpy RunRecord agreement (rtol, atol); see module
# docstring. The x64 bound absorbs FMA contraction over ~1e5-step clock
# accumulations; the f32 bound is the honest precision of a float32
# event clock and is only ever used when jax lacks 64-bit support.
X64_TOLERANCE = (1e-9, 1e-12)
F32_TOLERANCE = (2e-3, 1e-4)

_STATE = {"enabled": None}


def enable_x64() -> bool:
    """Idempotently enable ``jax_enable_x64``; returns True iff 64-bit
    mode is active afterwards (False when jax is missing or refuses)."""
    if _STATE["enabled"] is None:
        try:
            import jax
            jax.config.update("jax_enable_x64", True)
            _STATE["enabled"] = bool(
                getattr(jax.config, "jax_enable_x64", False))
        except Exception:                          # pragma: no cover
            _STATE["enabled"] = False
    return _STATE["enabled"]


def active_x64() -> bool:
    """True iff `enable_x64` has run and 64-bit mode is active."""
    return bool(_STATE["enabled"])


def jit_tolerance() -> Tuple[float, float]:
    """(rtol, atol) for jit-vs-numpy RunRecord comparisons under the
    currently active precision (call `enable_x64` first)."""
    return X64_TOLERANCE if active_x64() else F32_TOLERANCE


def record_mismatches(oracle: Sequence, got: Sequence) -> List[tuple]:
    """Fields where jit RunRecords leave the numpy oracle's: float fields
    beyond `jit_tolerance()` (NaN must meet NaN), other fields unequal,
    or a length mismatch. Empty means the records agree."""
    if len(oracle) != len(got):
        return [("n_records", len(oracle), len(got))]
    rtol, atol = jit_tolerance()
    bad = []
    for a, b in zip(oracle, got):
        da, db = dataclasses.asdict(a), dataclasses.asdict(b)
        for key, va in da.items():
            vb = db.get(key)
            if isinstance(va, float):
                if math.isnan(va):
                    ok = isinstance(vb, float) and math.isnan(vb)
                else:
                    ok = abs(va - vb) <= rtol * abs(va) + atol
            else:
                ok = va == vb
            if not ok:
                bad.append((a.model, a.lam, key, va, vb))
    return bad
