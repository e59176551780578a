"""Pallas TPU paged-attention decode kernel.

The vLLM PagedAttention idea adapted to TPU (DESIGN §3): there is no
pointer-chasing on TPU, so the page table becomes a *scalar-prefetched*
int32 tensor that drives the BlockSpec index_map — each grid step DMAs one
KV page from the HBM pool into VMEM based on block_tables[b, p]. Flash-
decoding style running max/denominator accumulate across pages in VMEM
scratch; invalid tail pages are skipped with @pl.when.

The pool is head-major so that every block spans the full last two
dimensions of its array, (page, D) and (G, D): Mosaic requires a block's
last two dims to be divisible by (8, 128) or equal to the array's, and it
refuses a block that cuts the Hkv axis out of a (P, page, Hkv, D) pool.

Grid: (B, Hkv, max_pages), pages innermost/sequential.
  q:      (B, Hkv, G, D)    -> block (1, 1, G, D) for the grid's kv head
  k_pool: (Hkv, P, page, D) -> block (1, 1, page, D) at page block_tables[b,p]
  out:    (B, Hkv, G, D)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_kernel(bt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref,
               m_sc, l_sc, acc_sc, *, page: int, num_pages: int,
               sm_scale: float):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    seq_len = sl_ref[b]

    @pl.when(p * page < seq_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)                # (page, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (G, page)
        pos = p * page + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            pr, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(p == num_pages - 1)
    def _finish():
        o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_kernel(q, k_pool, v_pool, block_tables, seq_lens, *,
                           interpret: bool = False):
    """q: (B, Hq, D); pools: (Hkv, P, page, D); block_tables: (B, max_pages);
    seq_lens: (B,). Returns (B, Hq, D)."""
    B, Hq, D = q.shape
    Hkv, _, page, _ = k_pool.shape
    max_pages = block_tables.shape[1]
    G = Hq // Hkv
    sm_scale = 1.0 / (D ** 0.5)

    kernel = functools.partial(_pa_kernel, page=page, num_pages=max_pages,
                               sm_scale=sm_scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, p, bt, sl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, p, bt, sl: (h, bt[b, p], 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, p, bt, sl: (h, bt[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, p, bt, sl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    # Mosaic has no 64-bit types: trace in 32-bit mode even where
    # the process enabled x64 (the compiled fleet does)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            name="paged_attention",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=interpret,
        )(block_tables, seq_lens, q.reshape(B, Hkv, G, D), k_pool, v_pool)
    return out.reshape(B, Hq, D)
