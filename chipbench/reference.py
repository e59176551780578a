"""Plain float32 reference of the served models, and the logit-gap check.

It imports nothing of the program. It draws the served weights from the
seed (`chipbench/weights.py`) one layer at a time and turns them into the
published form in float32 (`weights.published`), so that it never holds
more than one layer's weights. Every matmul runs at `highest` precision.

The forward is the published decoder of pre-norm blocks: the embedding
times its multiplier; per layer RMSNorm, GQA attention with half-split
RoPE and a causal softmax of the scores times the attention multiplier,
its output times the residual multiplier added back, RMSNorm, then a
SwiGLU MLP or a top-k mixture of SwiGLU experts whose gates are the
router's softmax over all experts, renormalised over the k chosen
(dropless), times the residual multiplier; then a final RMSNorm and the
unembed (tied to the embedding, or its own matrix), divided by the
logits scaling. A configuration that states no multipliers has 1, and
the attention scale 1/sqrt(head_dim).

`served_gaps` runs the reference over each prompt with the tokens that
were served for it, teacher-forced, and returns for each served token
the gap by which its reference logit lies below the reference's best
logit at that position. A greedy server that computes the model exactly
serves gaps of zero; rounding gives small gaps.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

BATCH = 4                       # sequences per reference call


def dims(cj: dict) -> tuple:
    """The model's dimensions as sorted (name, value) pairs: hashable, so
    they can be a static jit argument."""
    m = dict(d=cj["hidden_size"], hq=cj["num_attention_heads"],
             hkv=cj["num_key_value_heads"], hd=weights.head_dim(cj),
             layers=cj["num_hidden_layers"],
             experts=cj.get("num_local_experts", 0),
             top_k=cj.get("num_experts_per_tok", 0),
             theta=float(cj["rope_theta"]), eps=float(cj["rms_norm_eps"]),
             tied=bool(cj["tie_word_embeddings"]),
             **{f"m_{k}": v for k, v in weights.multipliers(cj).items()})
    return tuple(sorted(m.items()))


def _published(cj: dict, tree):
    return {k: weights.published(cj, k, v) for k, v in tree.items()}


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, theta):
    """x (N, L, H, D), positions 0..L-1, halves rotated as pairs."""
    L, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def attend(x, p, m: dict):
    """x (N, L, d) float32 plus its pre-norm attention sublayer, times the
    residual multiplier."""
    N, L, d = x.shape
    hq, hkv, hd = m["hq"], m["hkv"], m["hd"]
    h = _rms(x, m["eps"])
    q = _rope((h @ p["wq"]).reshape(N, L, hq, hd), m["theta"])
    k = _rope((h @ p["wk"]).reshape(N, L, hkv, hd), m["theta"])
    v = (h @ p["wv"]).reshape(N, L, hkv, hd)
    kv_of = jnp.arange(hq) // (hq // hkv)          # query head -> kv head
    k, v = k[:, :, kv_of], v[:, :, kv_of]
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) * m["m_attention"]
    causal = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1), v)
    return x + m["m_residual"] * (o.reshape(N, L, hq * hd) @ p["wo"])


@functools.partial(jax.jit, static_argnames=("dm",))
def _block(x, p, dm):
    """One decoder layer on (N, L, d) float32."""
    m = dict(dm)
    x = attend(x, p, m)
    N, L, d = x.shape
    h = _rms(x, m["eps"]).reshape(N * L, d)
    if m["experts"]:
        probs = jax.nn.softmax(h @ p["router"], -1)
        top, idx = jax.lax.top_k(probs, m["top_k"])
        top = top / jnp.sum(top, -1, keepdims=True)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(N * L)[:, None], idx].set(top)
        hid = (jax.nn.silu(jnp.einsum("nd,edf->enf", h, p["gate"]))
               * jnp.einsum("nd,edf->enf", h, p["up"]))
        y = jnp.einsum("ne,enf,efd->nd", gates, hid, p["down"])
    else:
        y = _swiglu(h, p["gate"], p["up"], p["down"])
    return x + m["m_residual"] * y.reshape(N, L, d)


@functools.partial(jax.jit, static_argnames=("dm",))
def _gaps(x, rows, cols, served, emb, final_scale, dm):
    """Gap of each picked served token below the best logit there."""
    m = dict(dm)
    h = _rms(x[rows, cols], m["eps"]) * final_scale
    logits = (h @ (emb["embed"].T if m["tied"] else emb["head"])
              / m["m_logits"])
    return (jnp.max(logits, -1)
            - jnp.take_along_axis(logits, served[:, None], -1)[:, 0])


def served_gaps(cj: dict, seed: int, seqs: Sequence[Sequence[int]],
                prompt_lens: Sequence[int], length: int,
                block=_block) -> np.ndarray:
    """The logit gap of every served token of `seqs` (prompt + served
    tokens, each at most `length` long), computed `BATCH` sequences at a
    time at fixed shapes, so that every run reuses the same compiled
    programs. `block(x, layer's published weights, dims(cj))` is one
    layer; an architecture module hands its own."""
    dm = dims(cj)
    emb = _published(cj, weights.embed_weights(cj, seed))
    final_scale = weights.published(cj, "final_norm", jnp.float32(1))
    emb_mult = dict(dm)["m_embedding"]
    with jax.default_matmul_precision("highest"):
        xs, picks = [], []
        for b0 in range(0, len(seqs), BATCH):
            ids = np.zeros((BATCH, length), np.int32)
            rows = np.zeros(BATCH * length, np.int32)
            cols = np.zeros(BATCH * length, np.int32)
            served = np.zeros(BATCH * length, np.int32)
            valid = np.zeros(BATCH * length, bool)
            n = 0
            for i, (s, pl) in enumerate(zip(seqs[b0:b0 + BATCH],
                                            prompt_lens[b0:b0 + BATCH])):
                s = np.asarray(s, np.int32)
                ids[i, :len(s) - 1] = s[:-1]
                k = len(s) - pl                 # served tokens of this row
                rows[n:n + k] = i
                cols[n:n + k] = np.arange(pl - 1, len(s) - 1)
                served[n:n + k] = s[pl:]
                valid[n:n + k] = True
                n += k
            xs.append(emb_mult * emb["embed"][jnp.asarray(ids)])
            picks.append(tuple(map(jnp.asarray, (rows, cols, served, valid))))
        for layer in range(dict(dm)["layers"]):
            p = _published(cj, weights.layer_weights(cj, seed, layer))
            xs = [block(x, p, dm) for x in xs]
        out = [np.asarray(_gaps(x, r, c, s, emb, final_scale, dm))
               [np.asarray(v)] for x, (r, c, s, v) in zip(xs, picks)]
    return np.concatenate(out)
