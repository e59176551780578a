"""Model assembly: decoder-only / hybrid / SSM / encoder-decoder stacks.

HLO stays compact for arbitrarily deep models via scan-over-superblocks: the
layer pattern repeats with period U (= lcm of attention interleave, MoE
interleave, sLSTM cadence); params for each of the U unit positions are
stacked over the R = L/U repeats and the stack is lax.scan'ed. Decode caches
are stacked the same way and stream through the scan as xs/ys.

Entry points (all pure; callers jit/pjit):
  init_params(rng, cfg)                  -> params
  train_loss(params, cfg, tokens, labels, ...) -> (loss, aux)
  prefill(params, cfg, tokens, ...)      -> (last_logits, cache)
  decode_step(params, cfg, token, cache, ...) -> (logits, cache)
  init_cache / abstract_cache            -> cache pytree (zeros / SDS)
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import (
    apply_mlp, apply_norm, cross_entropy_loss, dense_init, embed_init,
    init_mlp, init_norm, softcap)
from repro.parallel.sharding import constrain
from repro.quant import linear

MAX_POS = 32768          # learned-position table size (whisper)
AUX_LOSS_COEF = 0.01
VLM_PATCHES = 256        # stubbed patch count for vlm input cells


import dataclasses as _dc


@_dc.dataclass
class PerfConfig:
    """Beyond-baseline performance levers (§Perf hillclimb). The dry-run
    harness mutates the module-global PERF before tracing a cell."""
    kv_cache_dtype: Any = jnp.bfloat16   # fp8_e4m3 halves decode HBM reads
    local_recurrence: bool = False       # shard_map SSM/xLSTM scans: batch-
    #                                      local recurrence, no GSPMD
    #                                      permutes inside the time loop
    flash_decode: bool = False           # shard_map partial-softmax decode
    #                                      over the seq-sharded KV cache


PERF = PerfConfig()


# ---------------------------------------------------------------------------
# Superblock structure
# ---------------------------------------------------------------------------

def unit_size(cfg: ModelConfig) -> int:
    u = 1
    if cfg.attn_every > 1:
        u = math.lcm(u, cfg.attn_every)
    if cfg.moe is not None and cfg.moe.interleave > 1:
        u = math.lcm(u, cfg.moe.interleave)
    if cfg.xlstm is not None:
        u = math.lcm(u, cfg.xlstm.slstm_every)
    if cfg.num_layers % u:
        u = cfg.num_layers     # degenerate: no repetition -> single scan step
    return u


def unit_pattern(cfg: ModelConfig) -> Tuple[Tuple[str, bool], ...]:
    """(block_kind, is_moe) for each of the first U layers."""
    u = unit_size(cfg)
    kinds = cfg.block_pattern()
    moes = cfg.moe_layer_mask()
    return tuple((kinds[i], moes[i]) for i in range(u))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, kind: str, is_moe: bool, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"ln1": init_norm(cfg.norm_kind, d, dtype)}
    if kind == "attn":
        p["attn"] = attn.init_attention(
            ks[0], d, cfg.num_heads, cfg.num_kv_heads, hd, dtype)
    elif kind == "mamba":
        p["mamba"] = ssm_lib.init_mamba(ks[0], d, cfg.ssm, dtype)
    elif kind == "mlstm":
        p.update(xlstm_lib.init_mlstm(ks[0], d, cfg.num_heads, cfg.xlstm, dtype))
        return p    # self-contained (internal ff)
    elif kind == "slstm":
        p.update(xlstm_lib.init_slstm(ks[0], d, cfg.xlstm, dtype))
        return p
    if cfg.family == "encdec":
        p["ln_x"] = init_norm(cfg.norm_kind, d, dtype)
        p["xattn"] = attn.init_cross_attention(
            ks[1], d, cfg.num_heads, cfg.num_kv_heads, hd, dtype)
    # feed-forward half
    if is_moe and cfg.moe is not None:
        p["ln2"] = init_norm(cfg.norm_kind, d, dtype)
        p["moe"] = moe_lib.init_moe(ks[2], d, cfg.moe, cfg.mlp_kind, dtype)
    elif cfg.d_ff:
        p["ln2"] = init_norm(cfg.norm_kind, d, dtype)
        p["mlp"] = init_mlp(ks[2], d, cfg.d_ff, cfg.mlp_kind, dtype)
    return p


def init_params(rng, cfg: ModelConfig, dtype=None) -> Dict[str, Any]:
    dtype = dtype or jnp.bfloat16
    U = unit_size(cfg)
    R = cfg.num_layers // U
    pattern = unit_pattern(cfg)
    keys = jax.random.split(rng, cfg.num_layers + 8)

    blocks = []
    for j, (kind, is_moe) in enumerate(pattern):
        per_repeat = [
            _init_block(keys[r * U + j], cfg, kind, is_moe, dtype)
            for r in range(R)]
        blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_repeat))

    k_embed, k_head, k_pos, k_enc, *_ = jax.random.split(keys[-1], 8)
    params: Dict[str, Any] = {
        "embed": embed_init(k_embed, cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": init_norm(cfg.norm_kind, cfg.d_model, dtype),
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype)
    if cfg.rope_kind == "none" and cfg.family == "encdec":
        params["pos_embed"] = embed_init(k_pos, MAX_POS, cfg.d_model, dtype)
    if cfg.encoder_layers:
        ekeys = jax.random.split(k_enc, cfg.encoder_layers + 1)
        enc_layers = [
            {"ln1": init_norm(cfg.norm_kind, cfg.d_model, dtype),
             "attn": attn.init_attention(ekeys[i], cfg.d_model, cfg.num_heads,
                                         cfg.num_kv_heads,
                                         cfg.resolved_head_dim, dtype),
             "ln2": init_norm(cfg.norm_kind, cfg.d_model, dtype),
             "mlp": init_mlp(ekeys[i], cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                             dtype)}
            for i in range(cfg.encoder_layers)]
        params["encoder"] = jax.tree.map(lambda *xs: jnp.stack(xs), *enc_layers)
        params["enc_pos_embed"] = embed_init(
            ekeys[-1], cfg.frontend_len or 1500, cfg.d_model, dtype)
        params["enc_final_norm"] = init_norm(cfg.norm_kind, cfg.d_model, dtype)
    return params


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    return constrain(x, "batch", "seq", "d_model")


def unembed(params, cfg: ModelConfig, x, qcfg=None):
    if cfg.tie_embeddings:
        w = params["embed"]
        if isinstance(w, dict):
            q, s = w["q"], w["scale"]
            w = q if s is None else (q.astype(jnp.bfloat16) *
                                     s.astype(jnp.bfloat16))
        logits = jax.lax.dot_general(
            x, w.astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        logits = linear(x, params["lm_head"], qcfg).astype(jnp.float32)
    logits = softcap(logits, cfg.logit_softcap)
    return constrain(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# Block application — full-sequence (train / prefill)
# ---------------------------------------------------------------------------

def _apply_attn_block(p, cfg: ModelConfig, x, positions, qcfg,
                      enc_kv=None, make_cache=False):
    """Returns (x, cache_or_None). Cache k/v layout (B,Hkv,S,D)."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    h = apply_norm(p["ln1"], x, cfg.norm_kind)
    q, k, v = attn.qkv(p["attn"], h, cfg.num_heads, cfg.num_kv_heads, hd, qcfg)
    q = attn.rotate(cfg.rope_kind, q, positions, cfg.rope_theta)
    k = attn.rotate(cfg.rope_kind, k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "qheads", None)
    k = constrain(k, "batch", "seq", "kvheads", None)
    v = constrain(v, "batch", "seq", "kvheads", None)
    if S > attn.CHUNKED_THRESHOLD:
        o = attn.causal_attention_chunked(q, k, v)
    else:
        o = attn.causal_attention(q, k, v)
    o = linear(o.reshape(B, S, cfg.num_heads * hd), p["attn"]["wo"], qcfg)
    x = x + o
    cache = None
    if make_cache:
        kv_dt = PERF.kv_cache_dtype
        cache = {"k": k.transpose(0, 2, 1, 3).astype(kv_dt),
                 "v": v.transpose(0, 2, 1, 3).astype(kv_dt)}
    if enc_kv is not None:
        h = apply_norm(p["ln_x"], x, cfg.norm_kind)
        x = x + attn.cross_attention(
            p["xattn"], h, enc_kv["xk"], enc_kv["xv"],
            cfg.num_heads, cfg.num_kv_heads, hd, qcfg)
    return x, cache


def _apply_ff(p, cfg: ModelConfig, x, is_moe: bool, qcfg):
    aux = jnp.zeros((), jnp.float32)
    if is_moe and "moe" in p:
        with jax.named_scope("moe"):
            h = apply_norm(p["ln2"], x, cfg.norm_kind)
            mo, aux = moe_lib.apply_moe(p["moe"], h, cfg.moe, cfg.mlp_kind,
                                        qcfg)
            x = x + mo
    elif "mlp" in p:
        with jax.named_scope("mlp"):
            h = apply_norm(p["ln2"], x, cfg.norm_kind)
            x = x + apply_mlp(p["mlp"], h, cfg.mlp_kind, qcfg)
    return x, aux


def _local_batch_shard_map(fn, p, x):
    """Run a recurrent block under shard_map with batch-sharded activations
    and replicated params: the time-loop recurrence becomes provably local,
    eliminating the per-step collective-permutes GSPMD otherwise inserts
    (xlstm train baseline: 413 GB/step of permutes — §Perf cell C)."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.parallel.sharding import current_mesh, logical_spec
    mesh = current_mesh()
    if mesh is None:
        return fn(p, x)
    bspec = logical_spec(x.shape, ("batch",), mesh)
    bax = bspec[0] if len(bspec) else None
    if bax is None:
        # gate (optimized-sweep lesson, jamba long_500k): batch smaller
        # than the DP degree cannot be shard_map'd — keep the GSPMD path
        return fn(p, x)
    B = x.shape[0]
    out_abs = jax.eval_shape(fn, p, x)
    ospec = jax.tree.map(
        lambda s: P(bax) if (s.shape and s.shape[0] == B) else P(), out_abs)
    return shard_map(fn, mesh=mesh, in_specs=(P(), P(bax)),
                     out_specs=ospec, check_rep=False)(p, x)


def _apply_block_seq(p, cfg: ModelConfig, kind: str, is_moe: bool, x,
                     positions, qcfg, enc_kv=None, make_cache=False):
    """Full-sequence block application. Returns (x, aux, cache)."""
    cache = None
    if kind == "attn":
        x, cache = _apply_attn_block(p, cfg, x, positions, qcfg, enc_kv,
                                     make_cache)
    elif kind == "mamba":
        h = apply_norm(p["ln1"], x, cfg.norm_kind)
        fn = lambda p_, h_: ssm_lib.apply_mamba(p_, h_, cfg.ssm, qcfg)
        y, state = (_local_batch_shard_map(fn, p["mamba"], h)
                    if PERF.local_recurrence else fn(p["mamba"], h))
        x = x + y
        if make_cache:
            cache = state
    elif kind == "mlstm":
        h = apply_norm(p["ln1"], x, cfg.norm_kind)
        fn = lambda p_, h_: xlstm_lib.mlstm_seq(
            p_, h_, cfg.num_heads, cfg.xlstm, None, qcfg)
        y, state = (_local_batch_shard_map(fn, p, h)
                    if PERF.local_recurrence else fn(p, h))
        x = x + y
        if make_cache:
            cache = state
    elif kind == "slstm":
        h = apply_norm(p["ln1"], x, cfg.norm_kind)
        fn = lambda p_, h_: xlstm_lib.slstm_seq(p_, h_, cfg.xlstm, None,
                                                qcfg)
        y, state = (_local_batch_shard_map(fn, p, h)
                    if PERF.local_recurrence else fn(p, h))
        x = x + y
        if make_cache:
            cache = state
    x, aux = _apply_ff(p, cfg, x, is_moe, qcfg)
    x = constrain(x, "batch", "seq", "d_model")
    return x, aux, cache


def _stack_forward(params, cfg: ModelConfig, x, positions, qcfg,
                   enc_out=None, make_cache=False, remat=False):
    """Scan the superblock stack over R repeats.

    Returns (x, aux_sum, caches) — caches is a list over unit positions of
    (R,...)-stacked cache pytrees (or None when make_cache=False).
    """
    pattern = unit_pattern(cfg)
    U = len(pattern)
    hd = cfg.resolved_head_dim

    def body(x, stacked):
        aux_total = jnp.zeros((), jnp.float32)
        caches = []
        for j, (kind, is_moe) in enumerate(pattern):
            p = stacked[j]
            enc_kv = None
            if enc_out is not None and kind == "attn":
                # cross-attn K/V from encoder output, this layer's weights
                Bz, Se, _ = enc_out.shape
                k = linear(enc_out, p["xattn"]["wk"], qcfg).reshape(
                    Bz, Se, cfg.num_kv_heads, hd)
                v = linear(enc_out, p["xattn"]["wv"], qcfg).reshape(
                    Bz, Se, cfg.num_kv_heads, hd)
                enc_kv = {"xk": k, "xv": v}
            x, aux, cache = _apply_block_seq(
                p, cfg, kind, is_moe, x, positions, qcfg, enc_kv, make_cache)
            aux_total = aux_total + aux
            if make_cache:
                if enc_kv is not None:
                    cache = dict(cache or {}, **enc_kv)
                caches.append(cache if cache is not None else {})
        return x, (aux_total, tuple(caches))

    if remat:
        body = jax.checkpoint(body)

    x, (auxes, caches) = jax.lax.scan(body, x, params["blocks"])
    return x, jnp.sum(auxes), (list(caches) if make_cache else None)


# ---------------------------------------------------------------------------
# Encoder (whisper)
# ---------------------------------------------------------------------------

def encode(params, cfg: ModelConfig, frames, qcfg=None):
    """frames: (B, Se, d_model) stubbed frontend embeddings -> (B, Se, d)."""
    Se = frames.shape[1]
    x = frames + params["enc_pos_embed"][:Se].astype(frames.dtype)
    hd = cfg.resolved_head_dim

    def body(x, p):
        h = apply_norm(p["ln1"], x, cfg.norm_kind)
        q, k, v = attn.qkv(p["attn"], h, cfg.num_heads, cfg.num_kv_heads,
                           hd, qcfg)
        o = attn.bidirectional_attention(q, k, v)
        B, S = x.shape[:2]
        x = x + linear(o.reshape(B, S, cfg.num_heads * hd),
                       p["attn"]["wo"], qcfg)
        h = apply_norm(p["ln2"], x, cfg.norm_kind)
        x = x + apply_mlp(p["mlp"], h, cfg.mlp_kind, qcfg)
        return x, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return apply_norm(params["enc_final_norm"], x, cfg.norm_kind)


# ---------------------------------------------------------------------------
# Full forward / loss (train path)
# ---------------------------------------------------------------------------

def _positions(cfg: ModelConfig, batch: int, seq: int, offset=None):
    base = jnp.arange(seq, dtype=jnp.int32)[None]
    if offset is not None:
        base = base + offset[:, None]
    else:
        base = jnp.broadcast_to(base, (batch, seq))
    if cfg.rope_kind == "mrope":
        return jnp.broadcast_to(base[None], (3, batch, seq))
    return base


def forward(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            qcfg=None, remat=False):
    """Full-sequence logits. batch: tokens (B,S) [+ frames / patches]."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_tokens(params, cfg, tokens)
    if cfg.frontend == "vision_patches" and "patches" in batch:
        P_ = batch["patches"].shape[1]
        pat = jnp.pad(batch["patches"].astype(x.dtype),
                      ((0, 0), (0, S - P_), (0, 0)))
        is_patch = (jnp.arange(S) < P_)[None, :, None]
        x = jnp.where(is_patch, pat, x)
    if "pos_embed" in params:
        x = x + params["pos_embed"][:S].astype(x.dtype)
    positions = _positions(cfg, B, S)
    enc_out = None
    if cfg.encoder_layers:
        enc_out = encode(params, cfg, batch["frames"].astype(x.dtype), qcfg)
    x, aux, _ = _stack_forward(params, cfg, x, positions, qcfg,
                               enc_out=enc_out, remat=remat)
    x = apply_norm(params["final_norm"], x, cfg.norm_kind)
    return unembed(params, cfg, x, qcfg), aux


def train_loss(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
               qcfg=None, remat=True):
    logits, aux = forward(params, cfg, batch, qcfg, remat=remat)
    loss = cross_entropy_loss(logits, batch["labels"], batch.get("mask"))
    return loss + AUX_LOSS_COEF * aux, {"ce": loss, "aux": aux}


# ---------------------------------------------------------------------------
# Cache init
# ---------------------------------------------------------------------------

def _block_cache_shape(cfg: ModelConfig, kind: str, B: int, S_max: int,
                       R: int, enc_len: int = 0):
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    out = {}
    if kind == "attn":
        kv_dt = PERF.kv_cache_dtype
        out = {"k": ((R, B, cfg.num_kv_heads, S_max, hd), kv_dt),
               "v": ((R, B, cfg.num_kv_heads, S_max, hd), kv_dt)}
        if cfg.family == "encdec":
            out["xk"] = ((R, B, enc_len, cfg.num_kv_heads, hd), jnp.bfloat16)
            out["xv"] = ((R, B, enc_len, cfg.num_kv_heads, hd), jnp.bfloat16)
    elif kind == "mamba":
        di = cfg.ssm.expand * d
        out = {"conv": ((R, B, cfg.ssm.d_conv - 1, di), jnp.bfloat16),
               "h": ((R, B, di, cfg.ssm.d_state), jnp.float32)}
    elif kind == "mlstm":
        di = int(cfg.xlstm.mlstm_proj_factor * d)
        dh = di // cfg.num_heads
        out = {"C": ((R, B, cfg.num_heads, dh, dh), jnp.float32),
               "n": ((R, B, cfg.num_heads, dh), jnp.float32),
               "m": ((R, B, cfg.num_heads), jnp.float32)}
    elif kind == "slstm":
        out = {k: ((R, B, d), jnp.float32) for k in ("c", "n", "m", "h")}
    return out


def cache_spec(cfg: ModelConfig, batch: int, max_len: int, enc_len: int = 0):
    """Shape/dtype tree: {"len": (B,), "blocks": [per unit position]}."""
    U = unit_size(cfg)
    R = cfg.num_layers // U
    pattern = unit_pattern(cfg)
    blocks = [_block_cache_shape(cfg, kind, batch, max_len, R, enc_len)
              for kind, _ in pattern]
    return {"len": ((batch,), jnp.int32), "blocks": blocks}


def init_cache(cfg: ModelConfig, batch: int, max_len: int, enc_len: int = 0):
    spec = cache_spec(cfg, batch, max_len, enc_len)
    return jax.tree.map(lambda sd: jnp.zeros(sd[0], sd[1]), spec,
                        is_leaf=lambda x: isinstance(x, tuple) and
                        len(x) == 2 and isinstance(x[0], tuple))


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int,
                   enc_len: int = 0):
    spec = cache_spec(cfg, batch, max_len, enc_len)
    return jax.tree.map(lambda sd: jax.ShapeDtypeStruct(sd[0], sd[1]), spec,
                        is_leaf=lambda x: isinstance(x, tuple) and
                        len(x) == 2 and isinstance(x[0], tuple))


def constrain_cache(cfg: ModelConfig, cache):
    """Sharding constraints on the cache pytree (names per leaf rank)."""
    def visit(blocks):
        out = []
        for blk in blocks:
            c = {}
            for name, arr in blk.items():
                if name in ("k", "v"):
                    c[name] = constrain(arr, None, "batch", "kvheads",
                                        "kv_seq_tp", None)
                elif name in ("xk", "xv"):
                    c[name] = constrain(arr, None, "batch", None,
                                        "kvheads", None)
                elif name in ("conv", "h", "C", "n", "m", "c"):
                    names = [None, "batch"] + [None] * (arr.ndim - 2)
                    if name in ("h", "C") and arr.ndim >= 3:
                        names[2] = "ssm_inner"
                    c[name] = constrain(arr, *names)
                else:
                    c[name] = constrain(arr, None, "batch",
                                        *([None] * (arr.ndim - 2)))
            out.append(c)
        return out
    return {"len": cache["len"], "blocks": visit(cache["blocks"])}


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            qcfg=None, max_len: Optional[int] = None):
    """Run the full prompt, return (last-position logits, filled cache)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    max_len = max_len or S
    x = embed_tokens(params, cfg, tokens)
    if cfg.frontend == "vision_patches" and "patches" in batch:
        P_ = batch["patches"].shape[1]
        pat = jnp.pad(batch["patches"].astype(x.dtype),
                      ((0, 0), (0, S - P_), (0, 0)))
        x = jnp.where((jnp.arange(S) < P_)[None, :, None], pat, x)
    if "pos_embed" in params:
        x = x + params["pos_embed"][:S].astype(x.dtype)
    positions = _positions(cfg, B, S)
    enc_out = None
    if cfg.encoder_layers:
        enc_out = encode(params, cfg, batch["frames"].astype(x.dtype), qcfg)
    x, _, caches = _stack_forward(params, cfg, x, positions, qcfg,
                                  enc_out=enc_out, make_cache=True)
    # pad caches from S to max_len on the sequence axis
    pattern = unit_pattern(cfg)
    for j, (kind, _) in enumerate(pattern):
        if kind == "attn" and max_len > S:
            for nm in ("k", "v"):
                c = caches[j][nm]
                caches[j][nm] = jnp.pad(
                    c, ((0, 0), (0, 0), (0, 0), (0, max_len - S), (0, 0)))
    cache = {"len": jnp.full((B,), S, jnp.int32), "blocks": caches}
    cache = constrain_cache(cfg, cache)
    x = apply_norm(params["final_norm"], x[:, -1:], cfg.norm_kind)
    return unembed(params, cfg, x, qcfg), cache


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------

def _flash_decode_attention(q, kc, vc, cache_len):
    """shard_map flash-decoding over the cache's sequence shards: each
    model-axis shard computes a partial softmax over its local KV slice;
    the exact combine is three tiny psums of (num, den, max) instead of
    GSPMD's gather/reshard of multi-GB score tensors (§Perf cell B)."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.parallel.collectives import combine_partial_softmax
    from repro.parallel.sharding import current_mesh, logical_spec
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return attn.decode_attention(q, kc, vc, cache_len)
    bspec = logical_spec(q.shape, ("batch",), mesh)
    bax = bspec[0] if len(bspec) else None
    S = kc.shape[2]
    if S % mesh.shape["model"]:
        return attn.decode_attention(q, kc, vc, cache_len)
    # gate (optimized-sweep lesson, codeqwen1.5-7b): if the KV heads fully
    # occupy the model axis the cache is head-sharded, not seq-sharded —
    # forcing seq-shard flash-decode would reshard the cache every step.
    if kc.shape[1] % mesh.shape["model"] == 0:
        return attn.decode_attention(q, kc, vc, cache_len)
    # gate (jamba long_500k): at batch < DP degree the cache sequence is
    # sharded over (data, model); a model-axis-only shard_map would
    # UN-shard the data dimension — keep the GSPMD path.
    if bax is None:
        return attn.decode_attention(q, kc, vc, cache_len)

    def local(q_, kc_, vc_, cl_):
        i = jax.lax.axis_index("model")
        s_loc = kc_.shape[2]
        pos = i * s_loc + jnp.arange(s_loc)
        valid = pos[None, :] < cl_[:, None]
        num, den, m = attn.decode_attention_partial(q_, kc_, vc_, valid)
        out = combine_partial_softmax(num, den, m, "model")
        return out.astype(q_.dtype)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(bax), P(bax, None, "model"), P(bax, None, "model"),
                  P(bax)),
        out_specs=P(bax), check_rep=False)(q, kc, vc, cache_len)


def _apply_block_decode(p, cfg: ModelConfig, kind: str, is_moe: bool, x,
                        cache_j, positions, cache_len, qcfg):
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    new_cache = dict(cache_j)
    if kind == "attn":
        h = apply_norm(p["ln1"], x, cfg.norm_kind)
        q, k, v = attn.qkv(p["attn"], h, cfg.num_heads, cfg.num_kv_heads,
                           hd, qcfg)
        q = attn.rotate(cfg.rope_kind, q, positions, cfg.rope_theta)
        k = attn.rotate(cfg.rope_kind, k, positions, cfg.rope_theta)
        kc, vc = attn.update_cache(cache_j["k"], cache_j["v"],
                                   k.astype(cache_j["k"].dtype),
                                   v.astype(cache_j["v"].dtype), cache_len)
        if PERF.flash_decode:
            o = _flash_decode_attention(q, kc.astype(x.dtype),
                                        vc.astype(x.dtype), cache_len + 1)
        else:
            o = attn.decode_attention(q, kc.astype(x.dtype),
                                      vc.astype(x.dtype), cache_len + 1)
        x = x + linear(o.reshape(B, 1, cfg.num_heads * hd),
                       p["attn"]["wo"], qcfg)
        new_cache["k"], new_cache["v"] = kc, vc
        if "xk" in cache_j:
            h = apply_norm(p["ln_x"], x, cfg.norm_kind)
            x = x + attn.cross_attention(
                p["xattn"], h, cache_j["xk"].astype(x.dtype),
                cache_j["xv"].astype(x.dtype),
                cfg.num_heads, cfg.num_kv_heads, hd, qcfg)
    elif kind == "mamba":
        h = apply_norm(p["ln1"], x, cfg.norm_kind)
        y, st = ssm_lib.mamba_decode_step(
            p["mamba"], h, cache_j, cfg.ssm, qcfg)
        x = x + y
        new_cache = st
    elif kind == "mlstm":
        h = apply_norm(p["ln1"], x, cfg.norm_kind)
        y, st = xlstm_lib.mlstm_seq(p, h, cfg.num_heads, cfg.xlstm,
                                    cache_j, qcfg)
        x = x + y
        new_cache = st
    elif kind == "slstm":
        h = apply_norm(p["ln1"], x, cfg.norm_kind)
        y, st = xlstm_lib.slstm_seq(p, h, cfg.xlstm, cache_j, qcfg)
        x = x + y
        new_cache = st
    x, _ = _apply_ff(p, cfg, x, is_moe, qcfg)
    return x, new_cache


def decode_step(params, cfg: ModelConfig, token, cache, qcfg=None):
    """One decode step. token: (B,1) int32; cache from prefill/init_cache.

    Returns (logits (B,1,V), updated cache with len+1).
    """
    B = token.shape[0]
    cache_len = cache["len"]
    x = embed_tokens(params, cfg, token)
    if "pos_embed" in params:
        pos = jnp.take(params["pos_embed"], jnp.clip(cache_len, 0,
                                                     MAX_POS - 1), axis=0)
        x = x + pos[:, None].astype(x.dtype)
    positions = _positions(cfg, B, 1, offset=cache_len)
    pattern = unit_pattern(cfg)

    def body(x, xs):
        stacked_p, caches_r = xs
        new_caches = []
        for j, (kind, is_moe) in enumerate(pattern):
            x, nc = _apply_block_decode(
                stacked_p[j], cfg, kind, is_moe, x, caches_r[j],
                positions, cache_len, qcfg)
            new_caches.append(nc)
        return x, tuple(new_caches)

    x, new_blocks = jax.lax.scan(body, x, (params["blocks"],
                                           tuple(cache["blocks"])))
    x = apply_norm(params["final_norm"], x, cfg.norm_kind)
    logits = unembed(params, cfg, x, qcfg)
    new_cache = {"len": cache_len + 1, "blocks": list(new_blocks)}
    new_cache = constrain_cache(cfg, new_cache)
    return logits, new_cache
