"""Dispatching wrapper for the int8 matmul (kernel on TPU, ref elsewhere)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.int8_matmul.kernel import int8_matmul_kernel
from repro.kernels.int8_matmul.ref import int8_matmul_ref


def int8_matmul(x_q, w_q, x_scale, w_scale, *,
                use_kernel: Optional[bool] = None,
                interpret: bool = False):
    """Blocked quantized matmul; see kernel.py for shapes."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return int8_matmul_ref(x_q, w_q, x_scale, w_scale)
    xs = jnp.reshape(x_scale, (1,)).astype(jnp.float32)
    ws = jnp.reshape(w_scale, (1, -1)).astype(jnp.float32)
    return int8_matmul_kernel(x_q, w_q, xs, ws, interpret=interpret)
