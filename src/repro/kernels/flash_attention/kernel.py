"""Pallas TPU flash-attention (forward), GQA-aware, causal or full.

Tiling: grid (B, Hq, Sq/block_q, Sk/block_k) with the KV axis innermost and
sequential; running max / denominator / output accumulator live in VMEM
scratch and persist across KV iterations (re-initialized at kv_idx == 0).
Block shapes are MXU-aligned (multiples of 128 on the matmul dims wherever
the problem size allows). Causal blocks entirely above the diagonal are
skipped with @pl.when — the standard TPU FA schedule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
               sm_scale: float, causal: bool, block_q: int, block_k: int,
               num_kb: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (bq, bk)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_sc[...]                              # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    if causal:
        # skip KV blocks entirely above the causal diagonal
        @pl.when(ki * block_k <= (qi + 1) * block_q - 1)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == num_kb - 1)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D). Returns (B, Hq, Sq, D)."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    num_qb, num_kb = Sq // block_q, Sk // block_k
    sm_scale = 1.0 / (D ** 0.5)

    kernel = functools.partial(
        _fa_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_kb=num_kb)

    # Mosaic has no 64-bit types: trace in 32-bit mode even where
    # the process enabled x64 (the compiled fleet does)
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid=(B, Hq, num_qb, num_kb),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, qi, ki: (b, h, qi, 0)),
                pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
                pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, D),
                                   lambda b, h, qi, ki: (b, h, qi, 0)),
            out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),    # running max
                pltpu.VMEM((block_q, 1), jnp.float32),    # denominator
                pltpu.VMEM((block_q, D), jnp.float32),    # output accumulator
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(q, k, v)
