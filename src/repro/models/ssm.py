"""Selective SSM blocks: Mamba-1 and Mamba-2, prefill and O(1)-state
decode.

Mamba-1 (`SSMConfig.kind="mamba1"`) state carried between decode steps:
  conv: (B, d_conv-1, d_inner)   last inputs of the causal depthwise conv
  h:    (B, d_inner, d_state)    SSM hidden state (fp32)
Its prefill runs the recurrence with lax.scan over the sequence.

Mamba-2 (`kind="mamba2"`, the SSD layer): `in_proj` gives z, xBC and dt;
a causal depthwise conv (with bias) runs over xBC = x || B || C; each of
`n_heads` heads of `head_dim` channels has a scalar A, dt_bias and D; B
and C are shared by the heads of each of `n_groups` groups; the output is
RMSNorm(y * silu(z)) over all of d_inner, then `out_proj`. State:
  conv: (B, d_conv-1, d_inner + 2 * n_groups * d_state)
  h:    (B, n_heads / k, d_state, k * head_dim)   fp32, `k` heads packed
        along the last axis so that it spans the TPU's 128 lanes
        (`state_pack`); `unpack_state` gives (B, n_heads, head_dim,
        d_state).
Its prefill is the chunked SSD form (`ssd`): matmuls within a chunk, the
state carried between chunks. Its decode step is one pass over the state
(`kernels/ssm_decode`).

A prefill given `lens` returns each row's state at that row's own length
(rows are right-padded to a common length): dt is 0 at pad positions, so
exp(dt A) = 1 and dt x B = 0 leave the state as it was, and the conv tail
is gathered at the row's last d_conv-1 inputs.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.kernels.ssm_decode.ops import ssm_state_update
from repro.models.layers import dense_init, keep_rows
from repro.parallel.sharding import constrain
from repro.quant import linear

LANES = 128
HIGHEST = jax.lax.Precision.HIGHEST


def _dims(d_model: int, cfg: SSMConfig) -> Tuple[int, int]:
    di = cfg.expand * d_model
    dtr = cfg.dt_rank or -(-d_model // 16)
    return di, dtr


def init_mamba(key, d_model: int, cfg: SSMConfig, dtype=jnp.bfloat16) -> Dict:
    if cfg.kind == "mamba2":
        return _init_mamba2(key, d_model, cfg, dtype)
    di, dtr = _dims(d_model, cfg)
    ks = jax.random.split(key, 6)
    A = jnp.broadcast_to(jnp.arange(1, cfg.d_state + 1, dtype=jnp.float32),
                         (di, cfg.d_state))
    return {
        "in_proj": dense_init(ks[0], d_model, 2 * di, dtype),
        "conv_w": dense_init(ks[1], di, cfg.d_conv, dtype),
        "x_proj": dense_init(ks[2], di, dtr + 2 * cfg.d_state, dtype),
        "dt_proj": dense_init(ks[3], dtr, di, dtype),
        "dt_bias": jnp.zeros((di,), jnp.float32),
        "A_log": jnp.log(A),                       # (di, N) fp32
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(ks[5], di, d_model, dtype),
    }


def _conv_tail(xp, k: int, lens):
    """The last k-1 conv inputs of each row: xp is the (B, k-1+S, C) input
    with its k-1 leading zeros; a row of length n ends at xp[n + k-2]."""
    if lens is None:
        return xp[:, -(k - 1):, :]
    idx = lens[:, None] + jnp.arange(k - 1)[None]
    return jnp.take_along_axis(xp, idx[..., None].astype(jnp.int32), axis=1)


def _ssm_inputs(p, xz, cfg: SSMConfig, conv_state=None, qcfg=None,
                lens=None):
    """Shared front half: split, causal conv, input-dependent discretization.

    xz: (B, S, 2*di). Returns (u, dt, Bm, Cm, z, new_conv_state) where
      u (B,S,di), dt (B,S,di) fp32, Bm/Cm (B,S,N) fp32, z gate (B,S,di).
    With `lens` (B,), dt is 0 past each row's length and the conv state
    is taken at it.
    """
    di = xz.shape[-1] // 2
    x, z = jnp.split(xz, 2, axis=-1)
    k = cfg.d_conv
    # causal depthwise conv along S with state from previous steps
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], k - 1, di), x.dtype)
    else:
        pad = conv_state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                     # (B, S+k-1, di)
    new_conv = _conv_tail(xp, k, lens) if k > 1 else None
    w = _weight(p["conv_w"], x.dtype)                          # (di, k)
    u = sum(xp[:, i:i + x.shape[1], :] * w[:, i] for i in range(k))
    u = jax.nn.silu(u)

    proj = linear(u, p["x_proj"], qcfg).astype(jnp.float32)    # (B,S,dtr+2N)
    dtr = proj.shape[-1] - 2 * cfg.d_state
    dt_r, Bm, Cm = jnp.split(proj, [dtr, dtr + cfg.d_state], axis=-1)
    dt = jax.nn.softplus(
        linear(dt_r.astype(x.dtype), p["dt_proj"], qcfg).astype(jnp.float32)
        + p["dt_bias"])
    if lens is not None:
        dt = jnp.where(_valid(lens, x.shape[1])[..., None], dt, 0.0)
    return u, dt, Bm, Cm, z, new_conv


def _valid(lens, S: int):
    """(B, S) bool: positions before each row's length."""
    return jnp.arange(S)[None] < lens[:, None]


def _weight(wp, dtype):
    if isinstance(wp, dict):
        q, s = wp["q"], wp["scale"]
        return q.astype(dtype) if s is None else (
            q.astype(jnp.bfloat16) * s.astype(jnp.bfloat16)).astype(dtype)
    return wp.astype(dtype)


def mamba_scan_ref(u, dt, Bm, Cm, A, D, h0=None):
    """Reference selective scan: sequential over S in fp32.

    u (B,S,di); dt (B,S,di); Bm/Cm (B,S,N); A (di,N); D (di,).
    Returns (y (B,S,di) fp32, h_final (B,di,N) fp32).
    """
    Bsz, S, di = u.shape
    N = A.shape[-1]
    uf = u.astype(jnp.float32)
    h = jnp.zeros((Bsz, di, N), jnp.float32) if h0 is None else h0

    def step(h, xs):
        u_t, dt_t, B_t, C_t = xs
        dA = jnp.exp(dt_t[..., None] * A)                      # (B,di,N)
        dBu = (dt_t * u_t)[..., None] * B_t[:, None, :]        # (B,di,N)
        h = dA * h + dBu
        y = jnp.einsum("bdn,bn->bd", h, C_t) + D * u_t
        return h, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (uf, dt, Bm, Cm))
    h, ys = jax.lax.scan(step, h, xs)
    return jnp.moveaxis(ys, 0, 1), h


def apply_mamba(p, x, cfg: SSMConfig, qcfg=None, lens=None):
    """Train/prefill path. x: (B,S,d) -> (y (B,S,d), state dict); with
    `lens` (B,) the state is each row's at its own length."""
    if cfg.kind == "mamba2":
        return apply_mamba2(p, x, cfg, qcfg, lens)
    xz = linear(x, p["in_proj"], qcfg)
    xz = constrain(xz, "batch", None, "ssm_inner")
    u, dt, Bm, Cm, z, conv = _ssm_inputs(p, xz, cfg, None, qcfg, lens)
    A = -jnp.exp(p["A_log"])
    y, h = mamba_scan_ref(u, dt, Bm, Cm, A, p["D"])
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = linear(y, p["out_proj"], qcfg)
    state = {"conv": (conv.astype(jnp.bfloat16) if conv is not None else
                      jnp.zeros((x.shape[0], 0, u.shape[-1]), jnp.bfloat16)),
             "h": h}
    return out, state


def mamba_decode_step(p, x, state, cfg: SSMConfig, qcfg=None, active=None,
                      use_kernel: Optional[bool] = None):
    """Single-token decode. x: (B,1,d); state as `state_shapes` gives.
    Rows where `active` (B,) is False keep their state."""
    if cfg.kind == "mamba2":
        return mamba2_decode_step(p, x, state, cfg, qcfg, active, use_kernel)
    xz = linear(x, p["in_proj"], qcfg)
    u, dt, Bm, Cm, z, conv = _ssm_inputs(p, xz, cfg, state["conv"], qcfg)
    A = -jnp.exp(p["A_log"])
    y, h = mamba_scan_ref(u, dt, Bm, Cm, A, p["D"], h0=state["h"])
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = linear(y, p["out_proj"], qcfg)
    new = {"conv": conv.astype(jnp.bfloat16), "h": h}
    return out, new if active is None else keep_rows(active, new, state)


def state_shapes(d_model: int, cfg: SSMConfig,
                 dtype=jnp.bfloat16) -> Dict[str, Tuple]:
    """One slot's state: name -> (shape, dtype). A Mamba-2 conv tail is
    kept in the activations' `dtype`; h is always fp32."""
    if cfg.kind == "mamba2":
        di, H, P, G, N, conv = _m2_dims(d_model, cfg)
        k = state_pack(cfg)
        return {"conv": ((cfg.d_conv - 1, conv), dtype),
                "h": ((H // k, N, k * P), jnp.float32)}
    di, _ = _dims(d_model, cfg)
    return {"conv": ((cfg.d_conv - 1, di), jnp.bfloat16),
            "h": ((di, cfg.d_state), jnp.float32)}


# ---------------------------------------------------------------------------
# Mamba-2
# ---------------------------------------------------------------------------

def _m2_dims(d_model: int, cfg: SSMConfig):
    """(d_inner, heads, head_dim, groups, d_state, conv channels)."""
    di = cfg.expand * d_model
    H, P, G, N = cfg.n_heads, cfg.head_dim, cfg.n_groups, cfg.d_state
    assert H * P == di and H % G == 0, (H, P, di, G)
    return di, H, P, G, N, di + 2 * G * N


def state_pack(cfg: SSMConfig) -> int:
    """Heads packed along the state's last axis: as many as fill 128
    lanes, within one group of heads sharing B and C."""
    P = cfg.head_dim
    if P >= LANES or LANES % P:
        return 1
    return math.gcd(LANES // P, cfg.n_heads // cfg.n_groups)


def pack_state(h, k: int):
    """(B, H, P, N) -> (B, H/k, N, k*P)."""
    B, H, P, N = h.shape
    return h.reshape(B, H // k, k, P, N).transpose(0, 1, 4, 2, 3).reshape(
        B, H // k, N, k * P)


def unpack_state(hp, k: int):
    """(B, H/k, N, k*P) -> (B, H, P, N)."""
    B, H2, N, L = hp.shape
    P = L // k
    return hp.reshape(B, H2, N, k, P).transpose(0, 1, 3, 4, 2).reshape(
        B, H2 * k, P, N)


def _init_mamba2(key, d_model: int, cfg: SSMConfig, dtype) -> Dict:
    di, H, P, G, N, conv = _m2_dims(d_model, cfg)
    ks = jax.random.split(key, 4)
    return {
        "in_proj": dense_init(ks[0], d_model, di + conv + H, dtype),
        "conv_w": dense_init(ks[1], conv, cfg.d_conv, dtype),
        "conv_b": jnp.zeros((conv,), dtype),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "norm": jnp.ones((di,), dtype),
        "out_proj": dense_init(ks[3], di, d_model, dtype),
    }


def _m2_split(p, x, cfg: SSMConfig, qcfg):
    """in_proj -> z (B,S,di), xBC (B,S,conv), dt before softplus (B,S,H)."""
    di, H, _, _, _, conv = _m2_dims(x.shape[-1], cfg)
    zxd = linear(x, p["in_proj"], qcfg)
    return zxd[..., :di], zxd[..., di:di + conv], zxd[..., di + conv:]


def _m2_heads(xbc, cfg: SSMConfig, d_model: int):
    """Post-conv xBC -> x (..., H, P), B (..., G, N), C (..., G, N)."""
    di, H, P, G, N, _ = _m2_dims(d_model, cfg)
    lead = xbc.shape[:-1]
    return (xbc[..., :di].reshape(lead + (H, P)),
            xbc[..., di:di + G * N].reshape(lead + (G, N)),
            xbc[..., di + G * N:].reshape(lead + (G, N)))


def _gated_norm(p, y, z, eps: float):
    """RMSNorm(y * silu(z)) over d_inner, in fp32, times the norm's scale."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return (g * _weight(p["norm"], jnp.float32)).astype(z.dtype)


def ssd(x, dt, Bm, Cm, A, chunk: int, D=None, out_dtype=jnp.float32):
    """Chunked SSD scan.

    x (B,S,H,P); dt (B,S,H) fp32 (0 where a position is to leave the
    state alone); Bm/Cm (B,S,G,N); A (H,) fp32, negative; D (H,) the skip,
    or None. Returns (y (B,S,H,P) in `out_dtype`, h (B,H,P,N) fp32 after
    the last position).

    Within a chunk of Q positions, y_t = sum_{s<=t} exp(cs_t - cs_s) (C_t .
    B_s) dt_s x_s with cs the cumulative dt A: a (Q, Q) matmul a head,
    its operands in x's dtype and its sums in fp32. The state enters and
    leaves each chunk in fp32 at `highest` precision:
    y_t += exp(cs_t) C_t . h, h <- exp(cs_Q) h + sum_s exp(cs_Q - cs_s)
    dt_s x_s B_s^T. The chunk length changes rounding only.
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2:]
    Hg = H // G
    Q = min(chunk, S)
    nc = -(-S // Q)
    if nc * Q != S:
        pad = ((0, 0), (0, nc * Q - S))
        x, dt, Bm, Cm = (jnp.pad(t, pad + ((0, 0),) * (t.ndim - 2))
                         for t in (x, dt, Bm, Cm))
    f32, cdt = jnp.float32, x.dtype
    xg = x.reshape(Bsz, nc * Q, G, Hg, P)
    dtg = dt.reshape(Bsz, nc * Q, G, Hg)
    a = dtg * A.reshape(G, Hg)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None, None]
    h = jnp.zeros((Bsz, G, Hg, P, N), f32)

    def step(i, carry):
        h, y = carry
        sl = lambda t: jax.lax.dynamic_slice_in_dim(t, i * Q, Q, axis=1)  # noqa: E731
        cs = jnp.cumsum(sl(a), axis=1)                       # (B,Q,G,Hg)
        Bc, Cc = sl(Bm).astype(f32), sl(Cm).astype(f32)      # (B,Q,G,N)
        dtx = sl(xg).astype(f32) * sl(dtg)[..., None]        # (B,Q,G,Hg,P)
        seg = cs[:, :, None] - cs[:, None, :]                # (B,t,s,G,Hg)
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        cb = jnp.einsum("btgn,bsgn->btsg", Cc.astype(cdt), Bc.astype(cdt),
                        preferred_element_type=f32)
        w = (decay * cb[..., None]).astype(cdt)
        y_c = jnp.einsum("btsgh,bsghp->btghp", w, dtx.astype(cdt),
                         preferred_element_type=f32)
        y_c = y_c + jnp.exp(cs)[..., None] * jnp.einsum(
            "btgn,bghpn->btghp", Cc, h, precision=HIGHEST)
        if D is not None:
            y_c = y_c + D.reshape(G, Hg, 1) * sl(xg).astype(f32)
        to_end = jnp.exp(cs[:, -1:] - cs)[..., None] * dtx   # (B,Q,G,Hg,P)
        h = jnp.exp(cs[:, -1])[..., None, None] * h + jnp.einsum(
            "bsghp,bsgn->bghpn", to_end, Bc, precision=HIGHEST)
        y = jax.lax.dynamic_update_slice_in_dim(y, y_c.astype(y.dtype),
                                                i * Q, axis=1)
        return h, y

    y = jnp.zeros((Bsz, nc * Q, G, Hg, P), out_dtype)
    h, y = jax.lax.fori_loop(0, nc, step, (h, y))
    return y.reshape(Bsz, nc * Q, H, P)[:, :S], h.reshape(Bsz, H, P, N)


def ssd_ref(x, dt, Bm, Cm, A):
    """Sequential reference of `ssd`: one position at a time, fp32."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2:]
    rep = lambda t: jnp.repeat(t.astype(jnp.float32), H // G, axis=2)  # noqa: E731
    Bh, Ch = rep(Bm), rep(Cm)                                # (B,S,H,N)
    h = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def step(h, xs):
        x_t, dt_t, B_t, C_t = xs
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, C_t, precision=HIGHEST)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in
               (x.astype(jnp.float32), dt, Bh, Ch))
    h, ys = jax.lax.scan(step, h, xs)
    return jnp.moveaxis(ys, 0, 1), h


def _conv_full(xbc, p, k: int):
    """Causal depthwise conv with bias and silu over (B, S, C), in fp32;
    returns (activation in xbc's dtype, the zero-led input (B,k-1+S,C))."""
    S = xbc.shape[1]
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    w = _weight(p["conv_w"], jnp.float32)                    # (C, k)
    xf = xp.astype(jnp.float32)
    u = sum(xf[:, i:i + S] * w[:, i] for i in range(k))
    u = u + _weight(p["conv_b"], jnp.float32)
    return jax.nn.silu(u).astype(xbc.dtype), xp


def apply_mamba2(p, x, cfg: SSMConfig, qcfg=None, lens=None):
    """Mamba-2 prefill. x: (B,S,d) -> (y (B,S,d), state as
    `state_shapes`), the state at each row's length where `lens` is
    given, else at S."""
    d = x.shape[-1]
    B, S = x.shape[:2]
    with jax.named_scope("conv"):
        z, xbc, dt_raw = _m2_split(p, x, cfg, qcfg)
        act, xp = _conv_full(xbc, p, cfg.d_conv)
        # take the tail before the activation is used, so that the padded
        # input is not kept alive to the end of the step
        conv_state, act = jax.lax.optimization_barrier(
            (_conv_tail(xp, cfg.d_conv, lens), act))
        xs, Bm, Cm = _m2_heads(act, cfg, d)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
        if lens is not None:
            dt = jnp.where(_valid(lens, S)[..., None], dt, 0.0)
    with jax.named_scope("ssd"):
        y, h = ssd(xs, dt, Bm, Cm, -jnp.exp(p["A_log"]), cfg.chunk,
                   D=p["D"], out_dtype=x.dtype)
    with jax.named_scope("out"):
        g = _gated_norm(p, y.reshape(B, S, -1), z, cfg.norm_eps)
        out = linear(g, p["out_proj"], qcfg)
    return out, {"conv": conv_state.astype(x.dtype),
                 "h": pack_state(h, state_pack(cfg))}


def mamba2_decode_step(p, x, state, cfg: SSMConfig, qcfg=None, active=None,
                       use_kernel: Optional[bool] = None):
    """Mamba-2 single-token decode. x: (B,1,d). Rows where `active` is
    False keep their state: their dt is 0, which leaves h exactly as it
    was, and their conv tail is kept."""
    d = x.shape[-1]
    with jax.named_scope("conv"):
        z, xbc, dt_raw = _m2_split(p, x, cfg, qcfg)
        win = jnp.concatenate([state["conv"].astype(xbc.dtype), xbc], 1)
        w = _weight(p["conv_w"], jnp.float32)                # (C, k)
        u = jnp.einsum("bkc,ck->bc", win.astype(jnp.float32), w,
                       precision=HIGHEST)
        act = jax.nn.silu(u + _weight(p["conv_b"], jnp.float32))
        xs, Bm, Cm = _m2_heads(act.astype(xbc.dtype), cfg, d)
        dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])
        conv = win[:, 1:]
        if active is not None:
            dt = jnp.where(active[:, None], dt, 0.0)
            conv = jnp.where(active[:, None, None], conv, state["conv"])
    with jax.named_scope("state_update"):
        y, h = ssm_state_update(
            state["h"], xs.astype(jnp.float32), dt, -jnp.exp(p["A_log"]),
            Bm.astype(jnp.float32), Cm.astype(jnp.float32), p["D"],
            use_kernel=use_kernel)
    with jax.named_scope("out"):
        g = _gated_norm(p, y.reshape(x.shape[0], 1, -1), z, cfg.norm_eps)
        out = linear(g, p["out_proj"], qcfg)
    return out, {"conv": conv.astype(state["conv"].dtype), "h": h}
