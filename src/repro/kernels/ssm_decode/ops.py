"""Dispatching wrapper for the Mamba-2 decode state update (kernel on a
TPU, reference elsewhere)."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.ssm_decode.kernel import ssm_state_update_kernel
from repro.kernels.ssm_decode.ref import ssm_state_update_ref


def ssm_state_update(h, x, dt, A, Bm, Cm, D, *,
                     use_kernel: Optional[bool] = None,
                     interpret: bool = False):
    """Shapes as in ref.py. Returns (y (B, H, P) fp32, h).

    `use_kernel=None` picks the kernel on a TPU and the reference
    elsewhere; `interpret=True` (tests only) runs the kernel body on CPU."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return ssm_state_update_ref(h, x, dt, A, Bm, Cm, D)
    return ssm_state_update_kernel(h, x, dt, A, Bm, Cm, D,
                                   interpret=interpret)
