"""Pure-jnp oracle for paged attention: gather pages, then dense decode."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gather_kv(pool, block_tables):
    """pool: (Hkv, P, page, D); block_tables: (B, max_pages) ->
    contiguous (B, Hkv, max_pages*page, D)."""
    gathered = pool[:, block_tables]         # (Hkv, B, max_pages, page, D)
    H, B, n, pg, D = gathered.shape
    return gathered.reshape(H, B, n * pg, D).transpose(1, 0, 2, 3)


def paged_attention_ref(q, k_pool, v_pool, block_tables, seq_lens):
    """q: (B, Hq, D); pools: (Hkv, P, page, D); block_tables: (B, max_pages)
    int32; seq_lens: (B,) int32 valid context lengths.

    Returns (B, Hq, D).
    """
    B, Hq, D = q.shape
    k = gather_kv(k_pool, block_tables)      # (B, Hkv, S, D)
    v = gather_kv(v_pool, block_tables)
    Hkv = k.shape[1]
    G = Hq // Hkv
    S = k.shape[2]
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.float32(D))
    mask = jnp.arange(S)[None] < seq_lens[:, None]          # (B, S)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p.astype(v.dtype), v)
    # a row of length 0 (a free slot) attends to nothing: zeros, as the
    # kernel writes, not the mean of whatever its table names
    o = jnp.where(seq_lens[:, None, None, None] > 0, o, 0)
    return o.reshape(B, Hq, D).astype(q.dtype)
