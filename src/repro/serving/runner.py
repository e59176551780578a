"""Jitted model execution against the paged KV pool (real-execution tier).

Fixed-shape, mask-driven step functions over `max_batch` slots:
  prefill_fn — process the padded prompts of the rows it is given (one
               admitted request a call in `RealExecutor`), write their
               K/V into their pages and their states into their slots,
               emit the first sampled token (TTFT event).
  decode_fn  — one token for every active slot via the paged-attention op
               (the Pallas kernel on a TPU, the jnp reference elsewhere).
Named scopes tile both steps (`embed`; `attention`, with its K/V write in
`attention/kv_write`; `moe` or `mlp`; `unembed`), so a profile can
attribute device time to them; the names are what benchmarks read.

SSM / xLSTM / hybrid blocks keep per-slot O(1) states in the same state
pytree (they have no KV pages — the reason those archs run long_500k);
a prefill leaves each row's state at that row's own length. A Mamba
block runs under `mamba` (Mamba-2: `mamba/conv`, `mamba/ssd` in prefill,
`mamba/state_update` in decode, through the `ssm_decode` kernel on a TPU,
and `mamba/out`); a shared expert under `moe/shared`.
Encoder-decoder archs are not served by this engine (documented limitation;
the dry-run covers their serve path).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention.ops import paged_attention
from repro.models import attention as attn_lib
from repro.models import model as model_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import apply_norm, keep_rows
from repro.quant import linear


def init_pools(cfg: ModelConfig, num_pages: int, page_size: int,
               max_batch: int):
    """Device-side state pytree: KV page pools + per-slot SSM states."""
    U = model_lib.unit_size(cfg)
    R = cfg.num_layers // U
    hd = cfg.resolved_head_dim
    act = jnp.dtype(cfg.dtype)          # K/V and conv tails: activations'
    pools: List[Dict[str, Any]] = []
    for kind, _ in model_lib.unit_pattern(cfg):
        if kind == "attn":
            # head-major pages: the paged kernel's block layout
            shape = (R, cfg.num_kv_heads, num_pages, page_size, hd)
            pools.append({"k": jnp.zeros(shape, act),
                          "v": jnp.zeros(shape, act)})
        elif kind == "mamba":
            pools.append({
                name: jnp.zeros((R, max_batch) + shape, dtype)
                for name, (shape, dtype) in
                ssm_lib.state_shapes(cfg.d_model, cfg.ssm, act).items()})
        elif kind == "mlstm":
            di = int(cfg.xlstm.mlstm_proj_factor * cfg.d_model)
            dh = di // cfg.num_heads
            pools.append({
                "C": jnp.zeros((R, max_batch, cfg.num_heads, dh, dh),
                               jnp.float32),
                "n": jnp.zeros((R, max_batch, cfg.num_heads, dh),
                               jnp.float32),
                "m": jnp.full((R, max_batch, cfg.num_heads), -jnp.inf,
                              jnp.float32)})
        elif kind == "slstm":
            d = cfg.d_model
            pools.append({
                "c": jnp.zeros((R, max_batch, d), jnp.float32),
                "n": jnp.ones((R, max_batch, d), jnp.float32),
                "m": jnp.zeros((R, max_batch, d), jnp.float32),
                "h": jnp.zeros((R, max_batch, d), jnp.float32)})
    return pools


def _scatter_kv(pool_k, pool_v, k, v, block_tables, positions, active,
                page_size: int):
    """Scatter per-token K/V into pages of a (Hkv, P, page, D) pool.

    k/v: (B, T, Hkv, D); positions: (B, T) absolute token positions;
    active: (B, T) bool — inactive writes land on trash page 0.
    """
    B, T = positions.shape
    page_idx = positions // page_size                      # (B, T)
    offs = positions % page_size
    cols = jnp.clip(page_idx, 0, block_tables.shape[1] - 1)
    pages = jnp.take_along_axis(block_tables, cols, axis=1)  # (B, T)
    pages = jnp.where(active, pages, 0)
    pf, of = pages.reshape(-1), offs.reshape(-1)
    kf = k.reshape((-1,) + k.shape[2:]).swapaxes(0, 1)     # (Hkv, B*T, D)
    vf = v.reshape((-1,) + v.shape[2:]).swapaxes(0, 1)
    pool_k = pool_k.at[:, pf, of].set(kf.astype(pool_k.dtype))
    pool_v = pool_v.at[:, pf, of].set(vf.astype(pool_v.dtype))
    return pool_k, pool_v


def _write_kv(pool_k, pool_v, k, v, block_tables, active, page_size: int):
    """Write the rows' K/V into stacked (L, Hkv, P, page, D) pools: each
    (page, offset) an active token maps to takes that token's K/V, every
    other keeps its value. k/v: (L, R, T, Hkv, D); block_tables (R, P_max);
    active (R, T) bool.

    Each pool page takes its row page through a one-hot matmul, selected in
    one pass over the pool in the pool's own layout. (XLA:TPU lays a D=64
    pool out pages-minor; a scatter into its pages, as `_scatter_kv`
    makes, transposes the whole pool there and back.)
    """
    L, R, T, H, D = k.shape
    P = pool_k.shape[2]
    n = -(-T // page_size)                      # pages a row's tokens span
    pad = n * page_size - T
    act = jnp.pad(active, ((0, 0), (0, pad)))                 # (R, n*page)
    pos = jnp.arange(n * page_size)
    cols = jnp.minimum(pos // page_size, block_tables.shape[1] - 1)
    pages = jnp.where(act, block_tables[:, cols], P)      # P: not written
    # src[p, o]: the token (of the rows' R * n * page) that slot o of pool
    # page p takes, or -1
    src = jnp.full((P, page_size), -1, jnp.int32).at[
        pages, pos % page_size].set(
        jnp.arange(R * n * page_size, dtype=jnp.int32).reshape(R, -1),
        mode="drop")
    onehot = jax.nn.one_hot(src.max(axis=1) // page_size, R * n,
                            dtype=k.dtype)                     # (P, R*n)
    keep = (src < 0)[None, None, :, :, None]

    def put(pool, new):
        new = jnp.pad(new, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        # the matmul multiplies every row page by each pool page's 0 or 1:
        # a token that is not written must not carry a NaN into it
        new = jnp.where(act[None, :, :, None, None], new, 0)
        new = new.reshape(L, R * n, page_size, H, D).transpose(0, 3, 1, 2, 4)
        got = jnp.einsum("pj,lhjod->lhpod", onehot, new,
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
        return jnp.where(keep, pool, got.astype(pool.dtype))
    return put(pool_k, k), put(pool_v, v)


def make_decode_logits(cfg: ModelConfig, page_size: int, qcfg=None,
                       use_kernel: Optional[bool] = None):
    """One-token decode step, not jitted: `decode_logits(params, pools,
    block_tables, seq_lens, tokens, active)` with tokens (B,) int32
    returns (logits (B, V) f32, pools, seq_lens).

    `use_kernel=None` takes the Pallas paged kernel on a TPU and the jnp
    reference elsewhere."""
    if cfg.family == "encdec":
        raise NotImplementedError(
            "encoder-decoder serving uses the dry-run path only")
    pattern = model_lib.unit_pattern(cfg)
    hd = cfg.resolved_head_dim

    def decode_logits(params, pools, block_tables, seq_lens, tokens, active):
        B = tokens.shape[0]
        with jax.named_scope("embed"):
            x = model_lib.embed_tokens(params, cfg, tokens[:, None])
            positions = model_lib._positions(cfg, B, 1, offset=seq_lens)

        def body(x, xs):
            stacked_p, pools_r = xs
            new_pools = []
            for j, (kind, is_moe) in enumerate(pattern):
                p, pool = stacked_p[j], pools_r[j]
                if kind == "attn":
                    with jax.named_scope("attention"):
                        h = apply_norm(p["ln1"], x, cfg.norm_kind)
                        q, k, v = attn_lib.qkv(p["attn"], h, cfg.num_heads,
                                               cfg.num_kv_heads, hd, qcfg)
                        q = attn_lib.rotate(cfg.rope_kind, q, positions,
                                            cfg.rope_theta)
                        k = attn_lib.rotate(cfg.rope_kind, k, positions,
                                            cfg.rope_theta)
                        with jax.named_scope("kv_write"):
                            pk, pv = _scatter_kv(
                                pool["k"], pool["v"], k, v, block_tables,
                                seq_lens[:, None], active[:, None],
                                page_size)
                        o = paged_attention(
                            q[:, 0], pk.astype(x.dtype), pv.astype(x.dtype),
                            block_tables, seq_lens + active.astype(jnp.int32),
                            use_kernel=use_kernel)
                        x = x + linear(o.reshape(B, 1, cfg.num_heads * hd),
                                       p["attn"]["wo"], qcfg)
                    new_pools.append({"k": pk, "v": pv})
                else:
                    with jax.named_scope(kind):
                        h = apply_norm(p["ln1"], x, cfg.norm_kind)
                        if kind == "mamba":
                            y, st = ssm_lib.mamba_decode_step(
                                p["mamba"], h, pool, cfg.ssm, qcfg,
                                active=active, use_kernel=use_kernel)
                        elif kind == "mlstm":
                            y, st = xlstm_lib.mlstm_seq(
                                p, h, cfg.num_heads, cfg.xlstm, pool, qcfg)
                            st = keep_rows(active, st, pool)
                        else:
                            y, st = xlstm_lib.slstm_seq(p, h, cfg.xlstm,
                                                        pool, qcfg)
                            st = keep_rows(active, st, pool)
                        x = x + y
                        new_pools.append(st)
                x, _ = model_lib._apply_ff(p, cfg, x, is_moe, qcfg)
            return x, tuple(new_pools)

        x, new_pools = jax.lax.scan(body, x, (params["blocks"],
                                              tuple(pools)))
        with jax.named_scope("unembed"):
            x = apply_norm(params["final_norm"], x, cfg.norm_kind)
            logits = model_lib.unembed(params, cfg, x, qcfg)
        return (logits[:, 0], list(new_pools),
                seq_lens + active.astype(jnp.int32))

    return decode_logits


def make_step_fns(cfg: ModelConfig, page_size: int, qcfg=None,
                  use_kernel: Optional[bool] = None):
    """Build (prefill_fn, decode_fn) jitted closures for this config
    (`use_kernel` as in `make_decode_logits`)."""
    decode_logits = make_decode_logits(cfg, page_size, qcfg, use_kernel)
    pattern = model_lib.unit_pattern(cfg)
    hd = cfg.resolved_head_dim

    # -- decode -------------------------------------------------------------
    @jax.jit
    def decode_fn(params, pools, block_tables, seq_lens, tokens, active):
        """tokens: (B,) int32. Returns (next_tokens, pools, seq_lens)."""
        logits, pools, seq_lens = decode_logits(
            params, pools, block_tables, seq_lens, tokens, active)
        with jax.named_scope("unembed"):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, pools, seq_lens

    # -- prefill ------------------------------------------------------------
    @jax.jit
    def prefill_fn(params, pools, block_tables, seq_lens, tokens, lens,
                   do_prefill, slots=None):
        """Prefill the prompts of the call's rows: tokens (R, Lp) int32;
        lens, do_prefill and slots (R,); block_tables (R, P), the rows'
        own tables. `slots=None` means row r is slot r (a full-batch call).

        Returns (first_tokens (R,), pools, seq_lens): each flagged row's
        K/V in its pages, its recurrent state and its seq_len (= lens) at
        its slot. Unflagged rows write nothing.
        """
        R, Lp = tokens.shape
        if slots is None:
            slots = jnp.arange(R, dtype=jnp.int32)
        with jax.named_scope("embed"):
            x = model_lib.embed_tokens(params, cfg, tokens)
            positions = model_lib._positions(cfg, R, Lp)
            tok_active = (jnp.arange(Lp)[None] < lens[:, None]) & \
                do_prefill[:, None]

        # the scan carries the rows' activations only: each layer's K/V
        # or recurrent state leaves it as that layer's output
        def body(x, stacked_p):
            outs = []
            for j, (kind, is_moe) in enumerate(pattern):
                p = stacked_p[j]
                if kind == "attn":
                    with jax.named_scope("attention"):
                        h = apply_norm(p["ln1"], x, cfg.norm_kind)
                        q, k, v = attn_lib.qkv(p["attn"], h, cfg.num_heads,
                                               cfg.num_kv_heads, hd, qcfg)
                        q = attn_lib.rotate(cfg.rope_kind, q, positions,
                                            cfg.rope_theta)
                        k = attn_lib.rotate(cfg.rope_kind, k, positions,
                                            cfg.rope_theta)
                        o = attn_lib.causal_attention(q, k, v, kv_len=lens)
                        x = x + linear(o.reshape(R, Lp, cfg.num_heads * hd),
                                       p["attn"]["wo"], qcfg)
                    outs.append((k, v))
                else:
                    with jax.named_scope(kind):
                        h = apply_norm(p["ln1"], x, cfg.norm_kind)
                        if kind == "mamba":
                            y, st = ssm_lib.apply_mamba(p["mamba"], h,
                                                        cfg.ssm, qcfg,
                                                        lens=lens)
                        elif kind == "mlstm":
                            y, st = xlstm_lib.mlstm_seq(
                                p, h, cfg.num_heads, cfg.xlstm, None, qcfg)
                        else:
                            y, st = xlstm_lib.slstm_seq(p, h, cfg.xlstm,
                                                        None, qcfg)
                        x = x + y
                    outs.append(st)
                x, _ = model_lib._apply_ff(p, cfg, x, is_moe, qcfg)
            return x, tuple(outs)

        x, outs = jax.lax.scan(body, x, params["blocks"])

        # write once, after the scan: K/V into the rows' pages, states and
        # seq_lens at the rows' slots (unflagged rows' slots are dropped)
        dest = jnp.where(do_prefill, slots, seq_lens.shape[0])
        new_pools = []
        for (kind, _), pool, out in zip(pattern, pools, outs):
            if kind == "attn":
                with jax.named_scope("attention/kv_write"):
                    pk, pv = _write_kv(pool["k"], pool["v"], *out,
                                       block_tables, tok_active, page_size)
                new_pools.append({"k": pk, "v": pv})
            else:
                with jax.named_scope(kind):
                    new_pools.append(jax.tree.map(
                        lambda pl, st: pl.at[:, dest].set(
                            st.astype(pl.dtype), mode="drop"), pool, out))
        with jax.named_scope("unembed"):
            x = apply_norm(params["final_norm"], x, cfg.norm_kind)
            # logits at each request's last prompt position
            idx = jnp.clip(lens - 1, 0, Lp - 1)
            x_last = jnp.take_along_axis(
                x, idx[:, None, None].astype(jnp.int32), axis=1)
            logits = model_lib.unembed(params, cfg, x_last, qcfg)
            first = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            new_seq = seq_lens.at[dest].set(lens, mode="drop")
        return first, new_pools, new_seq

    return prefill_fn, decode_fn
