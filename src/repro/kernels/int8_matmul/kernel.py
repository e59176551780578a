"""Pallas TPU blocked int8 x int8 -> int32 matmul with fused dequant.

The Q-axis hot path (DESIGN §3): int8 is the natively-accelerated low-
precision MXU path on every TPU generation we model, so the framework's
int8 serving mode runs its projections through this kernel. Grid
(M/bm, N/bn, K/bk), K innermost/sequential, int32 accumulator in VMEM
scratch, dequantized once on the final K step (per-output-channel weight
scale x per-tensor activation scale) — the dequant never round-trips
through HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_sc, *, num_kb: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    acc_sc[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(ki == num_kb - 1)
    def _finish():
        scale = xs_ref[0] * ws_ref[0]                    # (bn,) fp32
        o_ref[...] = (acc_sc[...].astype(jnp.float32) *
                      scale[None, :]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def int8_matmul_kernel(x_q, w_q, x_scale, w_scale, *, block_m: int = 256,
                       block_n: int = 256, block_k: int = 256,
                       interpret: bool = False):
    """x_q: (M, K) int8; w_q: (K, N) int8; x_scale: (1,) fp32;
    w_scale: (1, N) fp32. Returns (M, N) fp32."""
    M, K = x_q.shape
    N = w_q.shape[1]
    block_m, block_n, block_k = (min(block_m, M), min(block_n, N),
                                 min(block_k, K))
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    num_kb = K // block_k

    kernel = functools.partial(_mm_kernel, num_kb=num_kb)
    # Mosaic has no 64-bit types: trace in 32-bit mode even where
    # the process enabled x64 (the compiled fleet does)
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            name="int8_matmul",
            grid=(M // block_m, N // block_n, num_kb),
            in_specs=[
                pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
                pl.BlockSpec((block_k, block_n), lambda mi, ni, ki: (ki, ni)),
                pl.BlockSpec((1,), lambda mi, ni, ki: (0,)),
                pl.BlockSpec((1, block_n), lambda mi, ni, ki: (0, ni)),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda mi, ni, ki: (mi, ni)),
            out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(x_q, w_q, x_scale, w_scale)
