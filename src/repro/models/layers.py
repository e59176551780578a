"""Shared neural-net building blocks (pure functional, explicit param pytrees).

Params are nested dicts of jnp arrays. Every `init_*` takes a PRNGKey and
returns a pytree; every `apply_*` is pure. Matmuls route through
`repro.quant.linear` so the Q axis (bf16 / int8 / fp8) applies uniformly.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.quant import linear

INIT_STD = 0.02


def dense_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16):
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * INIT_STD).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.bfloat16):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * INIT_STD).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(kind: str, d: int, dtype=jnp.bfloat16):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs: swiglu | relu2 (squared ReLU, Nemotron) | gelu
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, ff: int, kind: str, dtype=jnp.bfloat16):
    if ff == 0:
        return None
    ks = jax.random.split(key, 3)
    p = {"down": dense_init(ks[2], ff, d, dtype)}
    if kind == "swiglu":
        p["gate"] = dense_init(ks[0], d, ff, dtype)
        p["up"] = dense_init(ks[1], d, ff, dtype)
    else:
        p["up"] = dense_init(ks[0], d, ff, dtype)
    return p


def apply_mlp(p, x, kind: str, qcfg=None):
    if p is None:
        return jnp.zeros_like(x)
    if kind == "swiglu":
        h = jax.nn.silu(linear(x, p["gate"], qcfg)) * linear(x, p["up"], qcfg)
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(linear(x, p["up"], qcfg)))
    else:  # gelu
        h = jax.nn.gelu(linear(x, p["up"], qcfg), approximate=True)
    return linear(h, p["down"], qcfg)


def keep_rows(active, new, old):
    """Per-slot state select: rows where `active` (B,) holds take `new`,
    the others keep `old` (pytrees of arrays with a leading B axis)."""
    def pick(n, o):
        return jnp.where(active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
    return jax.tree.map(pick, new, old)


def softcap(logits, cap: float):
    if not cap:
        return logits
    return cap * jnp.tanh(logits / cap)


def unembed(emb_or_head, x, qcfg=None, transpose: bool = False):
    """Project hidden states to vocab logits. `transpose` for tied embeddings."""
    w = emb_or_head.T if transpose else emb_or_head
    return linear(x, w, qcfg)


def cross_entropy_loss(logits, labels, mask: Optional[jnp.ndarray] = None):
    """Token-mean cross entropy in fp32."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
