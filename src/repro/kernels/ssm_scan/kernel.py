"""Pallas TPU chunked selective scan (Mamba recurrence).

TPU adaptation of the CUDA selective-scan (DESIGN §3): instead of a warp-
level parallel prefix, the sequence is processed in VMEM-resident chunks
with the (di-blocked) SSM state carried in VMEM scratch across chunk
iterations — the grid's chunk axis is innermost/sequential, so for a fixed
(batch, di-block) the state never leaves VMEM. The channel axis is blocked
to bound the VMEM working set; N (d_state) stays whole (16-64).

Grid: (B, di/block_di, S/chunk) — chunk innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                 y_ref, hout_ref, h_sc, *, chunk: int, num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_sc[...] = h0_ref[0]

    A = a_ref[...]                                      # (bdi, N)
    Dv = d_ref[...]                                     # (bdi,)

    def step(t, h):
        u_t = u_ref[0, t, :]                            # (bdi,)
        dt_t = dt_ref[0, t, :]
        B_t = b_ref[0, t, :]                            # (N,)
        C_t = c_ref[0, t, :]
        dA = jnp.exp(dt_t[:, None] * A)                 # (bdi, N)
        h = dA * h + (dt_t * u_t)[:, None] * B_t[None, :]
        y_t = jnp.sum(h * C_t[None, :], axis=1) + Dv * u_t
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h_sc[...] = jax.lax.fori_loop(0, chunk, step, h_sc[...])

    @pl.when(ci == num_chunks - 1)
    def _finish():
        hout_ref[0] = h_sc[...]


@functools.partial(jax.jit, static_argnames=("chunk", "block_di",
                                             "interpret"))
def ssm_scan_kernel(u, dt, Bm, Cm, A, D, h0, *, chunk: int = 256,
                    block_di: int = 512, interpret: bool = False):
    """u/dt: (B, S, di) fp32; Bm/Cm: (B, S, N) fp32; A: (di, N) fp32;
    D: (di,) fp32; h0: (B, di, N) fp32.
    Returns (y (B, S, di) fp32, h_final (B, di, N) fp32)."""
    B, S, di = u.shape
    N = A.shape[1]
    chunk = min(chunk, S)
    block_di = min(block_di, di)
    assert S % chunk == 0 and di % block_di == 0
    num_chunks = S // chunk

    kernel = functools.partial(_scan_kernel, chunk=chunk,
                               num_chunks=num_chunks)
    # Mosaic has no 64-bit types: trace in 32-bit mode even where
    # the process enabled x64 (the compiled fleet does)
    with jax.enable_x64(False):
        y, h = pl.pallas_call(
            kernel,
            name="ssm_scan",
            grid=(B, di // block_di, num_chunks),
            in_specs=[
                pl.BlockSpec((1, chunk, block_di),
                             lambda b, dk, ci: (b, ci, dk)),
                pl.BlockSpec((1, chunk, block_di),
                             lambda b, dk, ci: (b, ci, dk)),
                pl.BlockSpec((1, chunk, N), lambda b, dk, ci: (b, ci, 0)),
                pl.BlockSpec((1, chunk, N), lambda b, dk, ci: (b, ci, 0)),
                pl.BlockSpec((block_di, N), lambda b, dk, ci: (dk, 0)),
                pl.BlockSpec((block_di,), lambda b, dk, ci: (dk,)),
                pl.BlockSpec((1, block_di, N), lambda b, dk, ci: (b, dk, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, chunk, block_di),
                             lambda b, dk, ci: (b, ci, dk)),
                pl.BlockSpec((1, block_di, N), lambda b, dk, ci: (b, dk, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, S, di), jnp.float32),
                jax.ShapeDtypeStruct((B, di, N), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((block_di, N), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=interpret,
        )(u, dt, Bm, Cm, A, D, h0)
    return y, h
