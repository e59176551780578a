"""Pallas TPU paged-attention decode kernel.

The vLLM PagedAttention idea adapted to TPU (DESIGN §3): there is no
pointer-chasing on TPU, so the page table becomes a *scalar-prefetched*
int32 tensor that drives the BlockSpec index maps. Flash-decoding style
running max/denominator accumulate across blocks in VMEM scratch.

Grid: (B, ceil(max_pages / ppb)), a row's blocks in order. One grid step
is one block of `ppb` pages of one row, every KV head at once:
  q:      (B, Hkv, G, D)    -> block (1, Hkv, G, D), the row's whole query
  k_pool: (Hkv, P, page, D) -> ppb operands, one per page of the block,
                               each a block (Hkv, 1, page, D) at the page
                               the step's table entry names
  out:    (B, Hkv, G, D)    -> block (1, Hkv, G, D), written at the row's
                               last grid step

The pages of a block are not contiguous in the pool, so the pool is passed
`ppb` times, operand j fetching page j of each block; Mosaic's pipeline
double-buffers every operand, fetching step s+1's pages behind step s's
compute. (A manual `make_async_copy` out of the pool cannot do this at
D=64: Mosaic refuses a DMA slice of an HBM array whose last dim is
narrower than its 128-lane tiling.)

The pipeline copies an operand only when its block index changes, so the
table the index maps read (`step_pages`) names, for a page at or past a
row's length, the page that operand already holds: a block whose first
token is at or past `seq_lens[b]` copies nothing and computes nothing, of
the last live block only the pages that hold live tokens are copied, and
a row of length 0 reads nothing and writes zeros. Tokens past the length
are masked.

`ppb` comes from the shapes alone: `BLOCK_TOKENS` tokens a block, never
more than `max_pages`, and halved until the double-buffered K/V blocks fit
`VMEM_BUDGET`. A grid step costs more than computing a block's masked
tokens does, so blocks are large: at 64 pages of 16 tokens a row is one
block. The q and out blocks span the last two dims of their arrays,
(G, D), which Mosaic accepts for any G and D; so does the (page, D) of a
pool block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_TOKENS = 1024           # tokens a grid step aims to cover
VMEM_BUDGET = 8 * 2 ** 20     # K/V blocks, double-buffered: half of the
#                               16 MiB a kernel may use by default on v5e
LANES = 128                   # VMEM pads a block's last dim to this


def pages_per_block(page: int, max_pages: int, num_kv_heads: int,
                    head_dim: int, itemsize: int) -> int:
    """Pages a grid step covers, from the shapes alone."""
    lanes = -(-head_dim // LANES) * LANES
    ppb = max(1, min(max_pages, BLOCK_TOKENS // page))
    while ppb > 1 and (2 * 2 * num_kv_heads * ppb * page * lanes
                       * itemsize) > VMEM_BUDGET:
        ppb //= 2
    return ppb


def step_pages(block_tables, seq_lens, page: int, ppb: int):
    """(B, nb * ppb) int32: the pool page operand j holds at grid step
    (b, i), entry [b, i * ppb + j]. A live page is the table's; a page at
    or past the row's length repeats what operand j held at the step
    before, so the pipeline copies nothing for it."""
    B, max_pages = block_tables.shape
    nb = -(-max_pages // ppb)
    bt = jnp.pad(block_tables, ((0, 0), (0, nb * ppb - max_pages)))
    live = (jnp.arange(nb * ppb)[None] * page < seq_lens[:, None])
    steps = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb, 1)
    # per operand, the last step at or before this one whose page is live
    last = jax.lax.cummax(
        jnp.where(live.reshape(B, nb, ppb), steps, -1).reshape(B * nb, ppb),
        axis=0)
    held = bt.reshape(B * nb, ppb)[jnp.maximum(last, 0),
                                   jnp.arange(ppb)[None]]
    return jnp.where(last >= 0, held, bt[0, 0]).reshape(B, nb * ppb)


def _pa_kernel(pages_ref, sl_ref, q_ref, *refs, page: int, ppb: int,
               sm_scale: float):
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, m_sc, l_sc, acc_sc = refs[2 * ppb:]
    b, i = pl.program_id(0), pl.program_id(1)
    hkv, g = q_ref.shape[1], q_ref.shape[2]
    bk = ppb * page
    seq_len = sl_ref[b]

    @pl.when(i == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(i * bk < seq_len)
    def _block():
        pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        for h in range(hkv):
            k = jnp.concatenate([r[h, 0] for r in k_refs], axis=0)  # (bk, D)
            v = jnp.concatenate([r[h, 0] for r in v_refs], axis=0)
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # (G, bk)
            s = jnp.where(pos < seq_len, s, NEG_INF)
            m_prev = m_sc[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_sc[h] = l_sc[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_sc[h] = acc_sc[h] * alpha + jax.lax.dot_general(
                p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[h] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        for h in range(hkv):
            o_ref[0, h] = (acc_sc[h] / jnp.maximum(l_sc[h], 1e-30)
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
    ppb = pages_per_block(page, max_pages, Hkv, D, k_pool.dtype.itemsize)

    kernel = functools.partial(_pa_kernel, page=page, ppb=ppb,
                               sm_scale=1.0 / (D ** 0.5))
    row = pl.BlockSpec((1, Hkv, G, D), lambda b, i, pg, sl: (b, 0, 0, 0))
    pool = [pl.BlockSpec((Hkv, 1, page, D),
                         lambda b, i, pg, sl, j=j: (0, pg[b, i * ppb + j],
                                                    0, 0))
            for j in range(ppb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pl.cdiv(max_pages, ppb)),
        in_specs=[row, *pool, *pool],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    # Mosaic has no 64-bit types: trace in 32-bit mode even where
    # the process enabled x64 (the compiled fleet does)
    with jax.enable_x64(False):
        pages = step_pages(block_tables, seq_lens, page, ppb)
        out = pl.pallas_call(
            kernel,
            name="paged_attention",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(pages, seq_lens, q.reshape(B, Hkv, G, D),
          *[k_pool] * ppb, *[v_pool] * ppb)
    return out.reshape(B, Hq, D)
