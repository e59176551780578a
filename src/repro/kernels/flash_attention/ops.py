"""Dispatching wrapper: Pallas kernel on TPU, jnp reference elsewhere.

Public layout matches the model zoo: (B, S, H, D). The kernel works in
(B, H, S, D); the wrapper transposes at the boundary (free on TPU — layout
assignment folds it into the surrounding ops).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import flash_attention_ref


def flash_attention(q, k, v, *, causal: bool = True,
                    use_kernel: Optional[bool] = None,
                    interpret: bool = False,
                    block_q: int = 128, block_k: int = 128):
    """q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D)."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return flash_attention_ref(q, k, v, causal=causal)
    Hq, G = q.shape[2], q.shape[2] // k.shape[2]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention_kernel(qt, kt, vt, causal=causal,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return o.transpose(0, 2, 1, 3)
