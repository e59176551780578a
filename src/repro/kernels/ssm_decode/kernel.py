"""Pallas TPU kernel: the fused Mamba-2 decode state update.

One pass over the fp32 state in HBM. For each slot and head,
    h <- exp(dt A) h + (dt x) B^T,    y = C . h + D x.

The state is packed (`models/ssm.py` `state_pack`): h (B, H/k, N, k*P),
k heads of P channels along the last axis, so that a (N, k*P) tile spans
the 128 lanes. Along that axis lie the per-channel operands (x, dt, A,
D as rows (H/k, k*P)); along N lie B and C, passed as columns (B, G, N,
1), so the update is a sublane broadcast times a lane broadcast and y a
sum over sublanes, all on the VPU in fp32.

Grid: (B, H/k / hb). A step holds `hb` packed rows of one slot, all of
one group of heads (one B and C column): blocks (1, hb, N, k*P) of the
state in and out, the state aliased in place, and (1, hb, k*P) of x, dt
and y. `hb` is the largest divisor of the rows per group whose state
block fits `BLOCK_BYTES`, a multiple of 8 or all of them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_BYTES = 2 ** 20       # one state block; in and out, double-buffered


def rows_per_block(rows_per_group: int, n: int, lanes: int) -> int:
    """Packed rows a grid step covers, from the shapes alone."""
    fits = [hb for hb in range(1, rows_per_group + 1)
            if rows_per_group % hb == 0
            and (hb % 8 == 0 or hb == rows_per_group)
            and hb * n * lanes * 4 <= BLOCK_BYTES]
    return max(fits) if fits else rows_per_group


def _kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, h_ref, y_ref,
            ho_ref):
    hb = h_ref.shape[1]
    x, dt = x_ref[0], dt_ref[0]                      # (hb, L)
    da = jnp.exp(dt * a_ref[...])
    dtx = dt * x
    dx = d_ref[...] * x
    b_col, c_col = b_ref[0, 0], c_ref[0, 0]          # (N, 1)
    for i in range(hb):
        h = da[i:i + 1] * h_ref[0, i] + b_col * dtx[i:i + 1]   # (N, L)
        ho_ref[0, i] = h
        y_ref[0, i:i + 1, :] = (jnp.sum(h * c_col, axis=0, keepdims=True)
                                + dx[i:i + 1])


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_state_update_kernel(h, x, dt, A, Bm, Cm, D, *,
                            interpret: bool = False):
    """h (B, H/k, N, k*P) fp32; x (B, H, P); dt (B, H); A, D (H,);
    Bm, Cm (B, G, N). Returns (y (B, H, P) fp32, h)."""
    from repro.kernels.ssm_decode.ref import lane_rows
    B, H2, N, L = h.shape
    H = x.shape[1]
    k = H // H2
    G = Bm.shape[1]
    per_group = H2 // G
    hb = rows_per_block(per_group, N, L)
    f32 = jnp.float32
    with jax.enable_x64(False):
        xr, dtr, Ar, Dr = (t.astype(f32) for t in
                           lane_rows(x, dt, A, D, k))
        cols = lambda t: t.astype(f32)[..., None]    # noqa: E731
        row = pl.BlockSpec((1, hb, L), lambda b, j: (b, j, 0))
        param = pl.BlockSpec((hb, L), lambda b, j: (j, 0))
        col = pl.BlockSpec((1, 1, N, 1),
                           lambda b, j: (b, j * hb // per_group, 0, 0))
        state = pl.BlockSpec((1, hb, N, L), lambda b, j: (b, j, 0, 0))
        y, h = pl.pallas_call(
            _kernel,
            name="ssm_state_update",
            grid=(B, H2 // hb),
            in_specs=[row, row, param, param, col, col, state],
            out_specs=[row, state],
            out_shape=[jax.ShapeDtypeStruct((B, H2, L), f32),
                       jax.ShapeDtypeStruct(h.shape, f32)],
            input_output_aliases={6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(xr, dtr, Ar, Dr, cols(Bm), cols(Cm), h)
    return y.reshape(x.shape), h
