"""The served weights, made from the seed on the device in one jitted
call, in bfloat16, in the layout the program's `init_params` gives.

They are a checkpoint in the program's form. The program has no scalar
multipliers; a configuration that states them (granite's embedding,
attention, residual and logits multipliers) is served with each folded
into the weights it scales, as converting such a checkpoint for a
runtime without them does (`folds`). Nor does it take a norm epsilon:
every RMSNorm it computes has 1e-6. A configuration that states another
(Phi-3's 1e-5) is served with its residual stream rescaled, which moves
the epsilon the norms see (`residual_scale`). `published` gives a matrix
back in the published form, in float32, for the reference, which
applies every multiplier, and the stated epsilon, where the published
model does.

The program's own initialiser (normal, std 0.02, norm scales 1) makes a
degenerate model: every layer adds little to a token's own embedding,
attention over the cache is nearly uniform, and the tied unembed then
repeats one token for ever, by a margin that no rounding or cache fault
could move. Greedy tokens would check nothing past the first. These
weights are scaled so that each layer adds as much as the residual
carries and attention is sharp: the served tokens then depend on the
whole context, and a lost cache entry, an altered token or a coarser
precision changes them.

Each matrix is uniform on 4096 levels with its standard deviation,
rounded to bfloat16, drawn from `fold_in(fold_in(PRNGKey(seed), layer),
matrix)`: integer random bits and one float multiply, so that the
reference (`layer_weights`) draws the same values one layer at a time.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict

import jax
import jax.numpy as jnp

LEVELS = 4096
EMBED = 64                      # the fold-in index of the (un)embedding
PROGRAM_NORM_EPS = 1e-6         # every RMSNorm of the program


def head_dim(cj: dict) -> int:
    """`head_dim` where the configuration states it, else d / heads."""
    return cj.get("head_dim") or cj["hidden_size"] // cj["num_attention_heads"]


def _dims(cj: dict):
    return (cj["hidden_size"], cj["num_attention_heads"],
            cj["num_key_value_heads"], head_dim(cj), cj["intermediate_size"],
            cj["vocab_size"], cj.get("num_local_experts", 0))


def stds(cj: dict) -> Dict[str, float]:
    """Standard deviation of each matrix, in the program's form (the
    multipliers folded in). Inputs to a matrix have an RMS of
    about 1 (they follow an RMSNorm), so 1/sqrt(fan_in) keeps its output
    at about 1; queries and keys get 1.5 times that, for scores with a
    spread of about 2: sharp attention. The router gets 3 times that:
    its top-k then holds most of the weight, so an expert that rounding
    swaps in or out at the k-th place moves the output little, and a
    coarser precision shows as more error, not as more swaps alone."""
    d, hq, hkv, hd, ff, vocab, experts = _dims(cj)
    return {"wq": 1.5 / math.sqrt(d), "wk": 1.5 / math.sqrt(d),
            "wv": 1 / math.sqrt(d), "wo": 1 / math.sqrt(hq * hd),
            "gate": 1 / math.sqrt(d), "up": 1 / math.sqrt(d),
            "down": 1 / math.sqrt(ff), "router": 3 / math.sqrt(d),
            "embed": 0.1, "head": 1 / math.sqrt(d)}


def _draw(key, shape, std):
    bits = jax.random.bits(key, shape, jnp.uint32)
    k = (bits >> 20).astype(jnp.int32) - LEVELS // 2
    scale = std / math.sqrt((LEVELS ** 2 - 1) / 12)
    return (k.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def multipliers(cj: dict) -> Dict[str, float]:
    """The configuration's scalar multipliers; a configuration that states
    none has 1, and the attention scale 1/sqrt(head_dim)."""
    hd = head_dim(cj)
    return {"embedding": float(cj.get("embedding_multiplier", 1.0)),
            "attention": float(cj.get("attention_multiplier", hd ** -0.5)),
            "residual": float(cj.get("residual_multiplier", 1.0)),
            "logits": float(cj.get("logits_scaling", 1.0))}


def residual_scale(cj: dict) -> float:
    """Factor c by which the program's residual stream is the published
    one: sqrt(program epsilon / published epsilon). RMSNorm of c x at
    epsilon c^2 e is RMSNorm of x at e, and nothing else in a block sees
    the stream's scale, so with the embedding and each layer's last
    matrices scaled by c the program computes, at its own epsilon, the
    published model at the stated one. A `program` block that names
    `rms_norm_eps` hands the program the epsilon itself."""
    program = cj.get("program", {}).get("rms_norm_eps", PROGRAM_NORM_EPS)
    return math.sqrt(float(program) / float(cj["rms_norm_eps"]))


def folds(cj: dict) -> Dict[str, float]:
    """Factor by which each matrix in the program's form is its published
    form scaled (program = published x factor), the multipliers folded
    in: the program scales scores by
    1/sqrt(head_dim) (queries carry the rest of the attention
    multiplier), adds each layer's output unscaled (its last matrix
    carries the residual multiplier) and looks up the embedding
    unscaled; the embedding and each layer's last matrix carry the
    residual stream's scale (`residual_scale`); the final norm's scale
    divides out what the tied unembed then carries, the embedding
    multiplier and the stream's scale, and the logits scaling. Norm
    scales are 1 in the program's form."""
    m = multipliers(cj)
    c = residual_scale(cj)
    tied = bool(cj["tie_word_embeddings"])
    return {"wq": m["attention"] * head_dim(cj) ** 0.5,
            "wo": m["residual"] * c, "down": m["residual"] * c,
            "embed": m["embedding"] * c,
            "final_norm": 1 / (m["logits"]
                               * (m["embedding"] * c if tied else 1))}


def published(cj: dict, name: str, w) -> jnp.ndarray:
    """Matrix `name` (or `final_norm`, the scale) of the program's form,
    in the published form, in float32."""
    return w.astype(jnp.float32) / folds(cj).get(name, 1.0)


def seed_key(seed: int):
    return jax.random.PRNGKey(seed)


def _sub(key, layer: int, i: int):
    return jax.random.fold_in(jax.random.fold_in(key, layer), i)


def layer_shapes(cj: dict) -> Dict[str, tuple]:
    d, hq, hkv, hd, ff, vocab, experts = _dims(cj)
    s = {"wq": (d, hq * hd), "wk": (d, hkv * hd), "wv": (d, hkv * hd),
         "wo": (hq * hd, d)}
    if experts:
        s.update(router=(d, experts), gate=(experts, d, ff),
                 up=(experts, d, ff), down=(experts, ff, d))
    else:
        s.update(gate=(d, ff), up=(d, ff), down=(ff, d))
    return s


class Config:
    """A configuration file as a static (hashable) jit argument."""

    def __init__(self, cj: dict):
        self.cj = cj
        self._key = json.dumps(cj, sort_keys=True)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Config) and self._key == other._key


def _layer(cj: dict, key, layer: int) -> Dict[str, jnp.ndarray]:
    sd = stds(cj)
    return {name: _draw(_sub(key, layer, i), shape, sd[name])
            for i, (name, shape) in enumerate(sorted(layer_shapes(cj).items()))}


def _embed(cj: dict, key) -> Dict[str, jnp.ndarray]:
    d, _, _, _, _, vocab, _ = _dims(cj)
    sd = stds(cj)
    out = {"embed": _draw(_sub(key, EMBED, 0), (vocab, d), sd["embed"])}
    if not cj["tie_word_embeddings"]:
        out["head"] = _draw(_sub(key, EMBED, 1), (d, vocab), sd["head"])
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_jit(c: Config, key, layer):
    """One program for every layer: `layer` is traced."""
    return _layer(c.cj, key, layer)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_jit(c: Config, key):
    return _embed(c.cj, key)


def layer_weights(cj: dict, seed: int, layer: int) -> Dict[str, jnp.ndarray]:
    """One layer's matrices, as served (bfloat16)."""
    return _layer_jit(Config(cj), seed_key(seed), jnp.uint32(layer))


def embed_weights(cj: dict, seed: int) -> Dict[str, jnp.ndarray]:
    """The embedding, and the unembed where it is not tied (bfloat16)."""
    return _embed_jit(Config(cj), seed_key(seed))


def program_params(cj: dict, seed: int):
    """The whole parameter tree the program serves, in one jitted call."""
    return _program_params(Config(cj), seed_key(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _program_params(c: Config, key):
    cj = c.cj
    d = cj["hidden_size"]
    layers = [_layer(cj, key, r) for r in range(cj["num_hidden_layers"])]
    n = len(layers)

    def stack(name):
        return jnp.stack([p[name] for p in layers])

    ones = jnp.ones((n, d), jnp.bfloat16)
    block = {"ln1": {"scale": ones},
             "attn": {k: stack(k) for k in ("wq", "wk", "wv", "wo")},
             "ln2": {"scale": ones}}
    if cj.get("num_local_experts"):
        block["moe"] = {"router": stack("router").astype(jnp.float32),
                        "experts_up": stack("up"),
                        "experts_down": stack("down"),
                        "experts_gate": stack("gate")}
    else:
        block["mlp"] = {k: stack(k) for k in ("gate", "up", "down")}
    emb = _embed(cj, key)
    params = {"embed": emb["embed"],
              "final_norm": {"scale": jnp.ones((d,), jnp.bfloat16)},
              "blocks": [block]}
    if "head" in emb:
        params["lm_head"] = emb["head"]
    return params
