"""Dispatching wrapper for the chunked selective scan."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ssm_scan.kernel import ssm_scan_kernel
from repro.kernels.ssm_scan.ref import ssm_scan_ref


def ssm_scan(u, dt, Bm, Cm, A, D, h0=None, *,
             use_kernel: Optional[bool] = None,
             interpret: bool = False,
             chunk: int = 256, block_di: int = 512):
    """Selective-scan dispatch; shapes per ref.py. Returns (y, h_final)."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if h0 is None:
        h0 = jnp.zeros((u.shape[0], u.shape[2], A.shape[1]), jnp.float32)
    if not use_kernel:
        return ssm_scan_ref(u, dt, Bm, Cm, A, D, h0)
    f32 = lambda a: a.astype(jnp.float32)
    return ssm_scan_kernel(f32(u), f32(dt), f32(Bm), f32(Cm), f32(A), f32(D),
                           f32(h0), chunk=chunk, block_di=block_di,
                           interpret=interpret)
