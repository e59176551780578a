"""Dispatching wrapper for paged attention (kernel on TPU, ref elsewhere)."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.paged_attention.kernel import paged_attention_kernel
from repro.kernels.paged_attention.ref import paged_attention_ref


def paged_attention(q, k_pool, v_pool, block_tables, seq_lens, *,
                    use_kernel: Optional[bool] = None,
                    interpret: bool = False):
    """q: (B, Hq, D); pools: (Hkv, P, page, D); block_tables: (B, max_pages)
    int32 page ids; seq_lens: (B,) int32. Returns (B, Hq, D).

    `use_kernel=None` picks the kernel on a TPU and the reference
    elsewhere; `interpret=True` (tests only) runs the kernel body on CPU."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return paged_attention_ref(q, k_pool, v_pool, block_tables, seq_lens)
    return paged_attention_kernel(q, k_pool, v_pool, block_tables, seq_lens,
                                  interpret=interpret)
