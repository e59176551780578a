"""Jitted model execution against the paged KV pool (real-execution tier).

Fixed-shape, mask-driven step functions over `max_batch` slots:
  prefill_fn — process padded prompts for newly admitted slots, scatter K/V
               into their pages, emit the first sampled token (TTFT event).
  decode_fn  — one token for every active slot via the paged-attention op
               (the Pallas kernel on a TPU, the jnp reference elsewhere).

SSM / xLSTM / hybrid blocks keep per-slot O(1) states in the same state
pytree (they have no KV pages — the reason those archs run long_500k).
Encoder-decoder archs are not served by this engine (documented limitation;
the dry-run covers their serve path).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention.ops import paged_attention
from repro.models import attention as attn_lib
from repro.models import model as model_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import apply_norm
from repro.quant import linear


def init_pools(cfg: ModelConfig, num_pages: int, page_size: int,
               max_batch: int):
    """Device-side state pytree: KV page pools + per-slot SSM states."""
    U = model_lib.unit_size(cfg)
    R = cfg.num_layers // U
    hd = cfg.resolved_head_dim
    pools: List[Dict[str, Any]] = []
    for kind, _ in model_lib.unit_pattern(cfg):
        if kind == "attn":
            # head-major pages: the paged kernel's block layout
            shape = (R, cfg.num_kv_heads, num_pages, page_size, hd)
            pools.append({"k": jnp.zeros(shape, jnp.bfloat16),
                          "v": jnp.zeros(shape, jnp.bfloat16)})
        elif kind == "mamba":
            di = cfg.ssm.expand * cfg.d_model
            pools.append({
                "conv": jnp.zeros((R, max_batch, cfg.ssm.d_conv - 1, di),
                                  jnp.bfloat16),
                "h": jnp.zeros((R, max_batch, di, cfg.ssm.d_state),
                               jnp.float32)})
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


def _mask_state(new, old, active):
    """Per-slot state update mask (active: (B,) bool)."""
    def pick(n, o):
        a = active.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(a, n, o)
    return jax.tree.map(pick, new, old)


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
        x = model_lib.embed_tokens(params, cfg, tokens[:, None])
        positions = model_lib._positions(cfg, B, 1, offset=seq_lens)

        def body(x, xs):
            stacked_p, pools_r = xs
            new_pools = []
            for j, (kind, is_moe) in enumerate(pattern):
                p, pool = stacked_p[j], pools_r[j]
                if kind == "attn":
                    h = apply_norm(p["ln1"], x, cfg.norm_kind)
                    q, k, v = attn_lib.qkv(p["attn"], h, cfg.num_heads,
                                           cfg.num_kv_heads, hd, qcfg)
                    q = attn_lib.rotate(cfg.rope_kind, q, positions,
                                        cfg.rope_theta)
                    k = attn_lib.rotate(cfg.rope_kind, k, positions,
                                        cfg.rope_theta)
                    pk, pv = _scatter_kv(
                        pool["k"], pool["v"], k, v, block_tables,
                        seq_lens[:, None], active[:, None], page_size)
                    o = paged_attention(
                        q[:, 0], pk.astype(x.dtype), pv.astype(x.dtype),
                        block_tables, seq_lens + active.astype(jnp.int32),
                        use_kernel=use_kernel)
                    x = x + linear(o.reshape(B, 1, cfg.num_heads * hd),
                                   p["attn"]["wo"], qcfg)
                    new_pools.append({"k": pk, "v": pv})
                else:
                    h = apply_norm(p["ln1"], x, cfg.norm_kind)
                    if kind == "mamba":
                        y, st = ssm_lib.mamba_decode_step(
                            p["mamba"], h, pool, cfg.ssm, qcfg)
                    elif kind == "mlstm":
                        y, st = xlstm_lib.mlstm_seq(
                            p, h, cfg.num_heads, cfg.xlstm, pool, qcfg)
                    else:
                        y, st = xlstm_lib.slstm_seq(p, h, cfg.xlstm, pool,
                                                    qcfg)
                    x = x + y
                    new_pools.append(_mask_state(st, pool, active))
                x, _ = model_lib._apply_ff(p, cfg, x, is_moe, qcfg)
            return x, tuple(new_pools)

        x, new_pools = jax.lax.scan(body, x, (params["blocks"],
                                              tuple(pools)))
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
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pools, seq_lens

    # -- prefill ------------------------------------------------------------
    @functools.partial(jax.jit, static_argnames=())
    def prefill_fn(params, pools, block_tables, seq_lens, tokens, lens,
                   do_prefill):
        """tokens: (B, Lpad) int32; lens: (B,); do_prefill: (B,) bool.

        Processes prompts for flagged slots; returns (first_tokens, pools,
        seq_lens) with seq_lens set to lens for those slots.
        """
        B, Lp = tokens.shape
        x = model_lib.embed_tokens(params, cfg, tokens)
        positions = model_lib._positions(cfg, B, Lp)
        tok_active = (jnp.arange(Lp)[None] < lens[:, None]) & \
            do_prefill[:, None]

        def body(x, xs):
            stacked_p, pools_r = xs
            new_pools = []
            for j, (kind, is_moe) in enumerate(pattern):
                p, pool = stacked_p[j], pools_r[j]
                if kind == "attn":
                    h = apply_norm(p["ln1"], x, cfg.norm_kind)
                    q, k, v = attn_lib.qkv(p["attn"], h, cfg.num_heads,
                                           cfg.num_kv_heads, hd, qcfg)
                    pos2 = model_lib._positions(cfg, B, Lp)
                    q = attn_lib.rotate(cfg.rope_kind, q, pos2,
                                        cfg.rope_theta)
                    k = attn_lib.rotate(cfg.rope_kind, k, pos2,
                                        cfg.rope_theta)
                    o = attn_lib.causal_attention(q, k, v, kv_len=lens)
                    x = x + linear(o.reshape(B, Lp, cfg.num_heads * hd),
                                   p["attn"]["wo"], qcfg)
                    posmat = jnp.broadcast_to(jnp.arange(Lp)[None], (B, Lp))
                    pk, pv = _scatter_kv(pool["k"], pool["v"], k, v,
                                         block_tables, posmat, tok_active,
                                         page_size)
                    new_pools.append({"k": pk, "v": pv})
                else:
                    h = apply_norm(p["ln1"], x, cfg.norm_kind)
                    if kind == "mamba":
                        y, st = ssm_lib.apply_mamba(p["mamba"], h, cfg.ssm,
                                                    qcfg)
                    elif kind == "mlstm":
                        y, st = xlstm_lib.mlstm_seq(
                            p, h, cfg.num_heads, cfg.xlstm, None, qcfg)
                    else:
                        y, st = xlstm_lib.slstm_seq(p, h, cfg.xlstm, None,
                                                    qcfg)
                    x = x + y
                    new_pools.append(_mask_state(st, pool, do_prefill))
                x, _ = model_lib._apply_ff(p, cfg, x, is_moe, qcfg)
            return x, tuple(new_pools)

        x, new_pools = jax.lax.scan(body, x, (params["blocks"],
                                              tuple(pools)))
        x = apply_norm(params["final_norm"], x, cfg.norm_kind)
        # logits at each request's last prompt position
        idx = jnp.clip(lens - 1, 0, Lp - 1)
        x_last = jnp.take_along_axis(
            x, idx[:, None, None].astype(jnp.int32), axis=1)
        logits = model_lib.unembed(params, cfg, x_last, qcfg)
        first = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        new_seq = jnp.where(do_prefill, lens, seq_lens)
        return first, list(new_pools), new_seq

    return prefill_fn, decode_fn
