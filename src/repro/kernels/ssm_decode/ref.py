"""Reference of the fused Mamba-2 decode state update, in jnp (fp32).

The state is packed: h (B, H/k, N, k*P) holds k heads of P channels
along its last axis (`models/ssm.py` `state_pack`). For each slot and
head: h <- exp(dt A) h + (dt x) B^T, then y = C . h + D x.
"""
from __future__ import annotations

import jax.numpy as jnp


def lane_rows(x, dt, A, D, k: int):
    """The per-channel operands as rows of the packed state's last axis:
    x (B, H, P), dt (B, H), A and D (H,) -> (B, H/k, k*P) each (A and D
    without the batch axis)."""
    B, H, P = x.shape
    per_ch = lambda t: jnp.repeat(t, P, axis=-1).reshape(  # noqa: E731
        t.shape[:-1] + (H // k, k * P))
    return x.reshape(B, H // k, k * P), per_ch(dt), per_ch(A), per_ch(D)


def group_cols(Bm, H2: int):
    """B or C (B, G, N) -> its group's for each packed row (B, H/k, N)."""
    return jnp.repeat(Bm, H2 // Bm.shape[1], axis=1)


def ssm_state_update_ref(h, x, dt, A, Bm, Cm, D):
    """h (B, H/k, N, k*P) fp32; x (B, H, P); dt (B, H); A, D (H,);
    Bm, Cm (B, G, N). Returns (y (B, H, P), h)."""
    H2 = h.shape[1]
    xr, dtr, Ar, Dr = lane_rows(x, dt, A, D, x.shape[1] // H2)
    Bc, Cc = group_cols(Bm, H2), group_cols(Cm, H2)
    h = (jnp.exp(dtr * Ar)[:, :, None, :] * h
         + Bc[..., None] * (dtr * xr)[:, :, None, :])
    y = jnp.sum(h * Cc[..., None], axis=2) + Dr * xr
    return y.reshape(x.shape), h
