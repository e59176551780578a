"""Host spans on the profiler's clock.

`span(name)` is a `jax.profiler.TraceAnnotation` where JAX is already
loaded, and a shared no-op context otherwise, so the sim tier never
imports JAX for it. With no profiler running, an annotation costs about
a microsecond.

The spans the serving tier opens (each once a call, never per token or
per request):
  executor.<prefill|decode>.inputs  host-to-device copies of the inputs
  executor.<prefill|decode>.launch  the jitted call, until it returns
  executor.<prefill|decode>.wait    the device sync and the copy back
  engine.admit                      submissions, admission, prompt matrix
  engine.update                     bookkeeping after a step
"""
from __future__ import annotations

import contextlib
import sys

_NO_SPAN = contextlib.nullcontext()


def span(name: str):
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(name)
