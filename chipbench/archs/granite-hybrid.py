"""granite-4.0-h-small's layers (`model_type` granitemoehybrid): the
served weights, the plain float32 reference and the counts.

Each layer is a pre-norm mixer, Mamba-2 or (where `layer_types` says
"attention") NoPE GQA attention, then a pre-norm mixture of experts plus
a shared SwiGLU expert; every branch is added back times
`residual_multiplier`. The chip holds `num_local_experts` experts of each
layer (the first ones) of the published count, `published.
num_local_experts`, which the router scores: its top-k logits, softmaxed
over the k chosen, weight the held experts' outputs, and the others are
left out, in the program and here alike.

Mamba-2 (as `GraniteMoeHybridMambaLayer.torch_forward` computes it):
in_proj gives z, xBC and dt; a causal depthwise conv with bias over
xBC = x || B || C, then silu; dt = softplus(dt + dt_bias); per head a
scalar A = -exp(A_log): h <- exp(dt A) h + dt x B^T, y = C . h + D x;
then RMSNorm(y * silu(z)) over all of d_inner (at rms_norm_eps) times its
scale, and out_proj. The reference runs that recurrence one position at
a time, with no chunking.

The weights are drawn as `chipbench/weights.py` draws them: uniform on
4096 levels, in bfloat16, from `fold_in(fold_in(PRNGKey(seed), layer),
matrix)`, in the program's form with the multipliers folded in (`folds`);
the reference divides them back out (`published`).
"""

import dataclasses
import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, reference, weights

DT_RANGE = (1e-3, 1e-1)         # dt at zero input, log-spaced over heads
A_RANGE = 16                    # A = 1..16 over heads, repeated
CONV_BIAS_STD = 0.1


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    kinds: tuple                # "mamba" | "attention" per layer
    hq: int
    hkv: int
    hd: int
    heads: int                  # Mamba-2 heads
    head_dim: int
    d_state: int
    groups: int
    d_conv: int
    experts: int                # held
    routed: int                 # the router's width
    top_k: int
    ff: int
    shared_ff: int
    vocab: int

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv(self) -> int:
        return self.d_inner + 2 * self.groups * self.d_state


def dims(cj: dict) -> Dims:
    d, hq = cj["hidden_size"], cj["num_attention_heads"]
    return Dims(
        d=d, kinds=tuple(cj["layer_types"][:cj["num_hidden_layers"]]),
        hq=hq, hkv=cj["num_key_value_heads"], hd=weights.head_dim(cj),
        heads=cj["mamba_n_heads"], head_dim=cj["mamba_d_head"],
        d_state=cj["mamba_d_state"], groups=cj["mamba_n_groups"],
        d_conv=cj["mamba_d_conv"], experts=cj["num_local_experts"],
        routed=cj["published"]["num_local_experts"],
        top_k=cj["num_experts_per_tok"], ff=cj["intermediate_size"],
        shared_ff=cj["shared_intermediate_size"], vocab=cj["vocab_size"])


def folds(cj: dict) -> Dict[str, float]:
    """`weights.folds`, and the residual multiplier (with the stream's
    scale) on the Mamba and shared-expert output projections too."""
    f = weights.folds(cj)
    return dict(f, out_proj=f["wo"], shared_down=f["wo"])


def layer_shapes(a: Dims, kind: str) -> Dict[str, tuple]:
    s = {"router": (a.d, a.routed), "gate": (a.experts, a.d, a.ff),
         "up": (a.experts, a.d, a.ff), "down": (a.experts, a.ff, a.d),
         "shared_gate": (a.d, a.shared_ff), "shared_up": (a.d, a.shared_ff),
         "shared_down": (a.shared_ff, a.d)}
    if kind == "attention":
        s.update(wq=(a.d, a.hq * a.hd), wk=(a.d, a.hkv * a.hd),
                 wv=(a.d, a.hkv * a.hd), wo=(a.hq * a.hd, a.d))
    else:
        s.update(in_proj=(a.d, a.d_inner + a.conv + a.heads),
                 conv_w=(a.conv, a.d_conv), conv_b=(a.conv,),
                 out_proj=(a.d_inner, a.d))
    return s


def stds(a: Dims) -> Dict[str, float]:
    r = lambda n: 1 / math.sqrt(n)  # noqa: E731
    return {"router": 3 * r(a.d), "gate": r(a.d), "up": r(a.d),
            "down": r(a.ff), "shared_gate": r(a.d), "shared_up": r(a.d),
            "shared_down": r(a.shared_ff), "wq": 1.5 * r(a.d),
            "wk": 1.5 * r(a.d), "wv": r(a.d), "wo": r(a.hq * a.hd),
            "in_proj": r(a.d), "conv_w": r(a.d_conv),
            "conv_b": CONV_BIAS_STD, "out_proj": r(a.d_inner)}


def head_params(a: Dims) -> Dict[str, jnp.ndarray]:
    """Mamba-2's per-head vectors (float32): A_log, dt_bias, D."""
    h = jnp.arange(a.heads, dtype=jnp.float32)
    lo, hi = map(math.log, DT_RANGE)
    dt = jnp.exp(lo + (hi - lo) * h / max(a.heads - 1, 1))
    return {"A_log": jnp.log(1.0 + h % A_RANGE),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),    # softplus^-1
            "D": jnp.ones((a.heads,), jnp.float32)}


def _layer(cj: dict, key, layer: int, kind: str):
    """One layer's matrices as served (bfloat16, program form)."""
    a = dims(cj)
    sd = stds(a)
    return {name: weights._draw(weights._sub(key, layer, i), shape, sd[name])
            for i, (name, shape) in enumerate(sorted(
                layer_shapes(a, kind).items()))}


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_jit(c: weights.Config, key, layer, kind: str):
    return _layer(c.cj, key, layer, kind)


def program_params(cj: dict, seed: int):
    """The whole parameter tree the program serves, in one jitted call."""
    return _program_params(weights.Config(cj), weights.seed_key(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _program_params(c: weights.Config, key):
    cj = c.cj
    a = dims(cj)
    blocks = []
    for i, kind in enumerate(a.kinds):
        w = _layer(cj, key, i, kind)
        ones = jnp.ones((a.d,), jnp.bfloat16)
        block = {"ln1": {"scale": ones}, "ln2": {"scale": ones},
                 "moe": {"router": w["router"].astype(jnp.float32),
                         "experts_gate": w["gate"],
                         "experts_up": w["up"], "experts_down": w["down"],
                         "shared": {"gate": w["shared_gate"],
                                    "up": w["shared_up"],
                                    "down": w["shared_down"]}}}
        if kind == "attention":
            block["attn"] = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
        else:
            block["mamba"] = dict(
                in_proj=w["in_proj"], conv_w=w["conv_w"], conv_b=w["conv_b"],
                out_proj=w["out_proj"],
                norm=jnp.ones((a.d_inner,), jnp.bfloat16), **head_params(a))
        # one repeat of the period: each position's stack holds one layer
        blocks.append(jax.tree.map(lambda t: t[None], block))
    emb = weights._embed(cj, key)
    return {"embed": emb["embed"],
            "final_norm": {"scale": jnp.ones((a.d,), jnp.bfloat16)},
            "blocks": blocks}


# -- the reference ------------------------------------------------------

def published(cj: dict, tree) -> Dict[str, jnp.ndarray]:
    f = folds(cj)
    return {k: v.astype(jnp.float32) / f.get(k, 1.0) for k, v in tree.items()}


def _rms(x, eps):
    return reference._rms(x, eps)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def moe(h, p, a: Dims):
    """The held experts' part of the routed sum, plus the shared expert:
    h (T, d) normed -> (T, d)."""
    top, idx = jax.lax.top_k(h @ p["router"], a.top_k)
    gates = jax.nn.softmax(top, -1)
    held = jnp.zeros((h.shape[0], a.routed), h.dtype).at[
        jnp.arange(h.shape[0])[:, None], idx].set(gates)[:, :a.experts]
    hid = (jax.nn.silu(jnp.einsum("nd,edf->enf", h, p["gate"]))
           * jnp.einsum("nd,edf->enf", h, p["up"]))
    y = jnp.einsum("ne,enf,efd->nd", held, hid, p["down"])
    return y + _swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])


def mamba(h, p, a: Dims, eps: float):
    """Mamba-2 mixer over (N, L, d) normed inputs, one position at a time."""
    N, L, _ = h.shape
    di, G, S = a.d_inner, a.groups, a.d_state
    proj = h @ p["in_proj"]
    z, xbc, dt = (proj[..., :di], proj[..., di:di + a.conv],
                  proj[..., di + a.conv:])
    xp = jnp.pad(xbc, ((0, 0), (a.d_conv - 1, 0), (0, 0)))
    u = sum(xp[:, i:i + L] * p["conv_w"][:, i] for i in range(a.d_conv))
    u = jax.nn.silu(u + p["conv_b"])
    x = u[..., :di].reshape(N, L, a.heads, a.head_dim)
    rep = lambda t: jnp.repeat(t.reshape(N, L, G, S),  # noqa: E731
                               a.heads // G, axis=2)
    B, C = rep(u[..., di:di + G * S]), rep(u[..., di + G * S:])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    def step(s, xs):
        x_t, dt_t, B_t, C_t = xs
        s = (jnp.exp(dt_t * A)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return s, jnp.einsum("nhps,nhs->nhp", s, C_t)

    s0 = jnp.zeros((N, a.heads, a.head_dim, S), jnp.float32)
    _, ys = jax.lax.scan(step, s0, tuple(jnp.moveaxis(t, 1, 0)
                                         for t in (x, dt, B, C)))
    y = jnp.moveaxis(ys, 0, 1) + p["D"][:, None] * x
    g = y.reshape(N, L, di) * jax.nn.silu(z)
    return (_rms(g, eps) * p["norm"]) @ p["out_proj"]


def attention(h, p, a: Dims, m_attention: float):
    """NoPE GQA, causal, scores times the attention multiplier."""
    N, L, _ = h.shape
    q = (h @ p["wq"]).reshape(N, L, a.hq, a.hd)
    kv_of = jnp.arange(a.hq) // (a.hq // a.hkv)
    k = (h @ p["wk"]).reshape(N, L, a.hkv, a.hd)[:, :, kv_of]
    v = (h @ p["wv"]).reshape(N, L, a.hkv, a.hd)[:, :, kv_of]
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) * m_attention
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1), v)
    return o.reshape(N, L, a.hq * a.hd) @ p["wo"]


@functools.partial(jax.jit, static_argnames=("a", "kind", "dm"))
def block(x, p, a: Dims, kind: str, dm):
    """One layer on (N, L, d) float32."""
    m = dict(dm)
    h = _rms(x, m["eps"])
    mix = (attention(h, p, a, m["m_attention"]) if kind == "attention"
           else mamba(h, p, a, m["eps"]))
    x = x + m["m_residual"] * mix
    N, L, d = x.shape
    y = moe(_rms(x, m["eps"]).reshape(N * L, d), p, a)
    return x + m["m_residual"] * y.reshape(N, L, d)


def layer_weights(cj: dict, seed: int, layer: int, kind: str):
    """One layer's published weights, float32."""
    a = dims(cj)
    w = _layer_jit(weights.Config(cj), weights.seed_key(seed),
                   jnp.uint32(layer), kind)
    w = published(cj, w)
    if kind != "attention":
        w.update(head_params(a), norm=jnp.ones((a.d_inner,), jnp.float32))
    return w


def served_gaps(cj: dict, seed: int, seqs: Sequence[Sequence[int]],
                prompt_lens: Sequence[int], length: int,
                block=block) -> np.ndarray:
    """The logit gap of every served token of `seqs` (prompt + served
    tokens), teacher-forced through the float32 reference, `reference.
    BATCH` sequences at a time at fixed shapes (`reference.served_gaps`);
    `block` is one layer."""
    a, dm = dims(cj), reference.dims(cj)
    emb = {k: weights.published(cj, k, v)
           for k, v in weights.embed_weights(cj, seed).items()}
    final_scale = weights.published(cj, "final_norm", jnp.float32(1))
    n = reference.BATCH
    with jax.default_matmul_precision("highest"):
        xs, picks = [], []
        for b0 in range(0, len(seqs), n):
            ids = np.zeros((n, length), np.int32)
            rows, cols, served = [], [], []
            for i, (s, pl) in enumerate(zip(seqs[b0:b0 + n],
                                            prompt_lens[b0:b0 + n])):
                ids[i, :len(s) - 1] = s[:-1]
                k = len(s) - pl
                rows += [i] * k
                cols += range(pl - 1, len(s) - 1)
                served += list(s[pl:])
            pad = n * length - len(rows)
            valid = np.arange(n * length) < len(rows)
            xs.append(dict(dm)["m_embedding"] * emb["embed"][jnp.asarray(ids)])
            picks.append(tuple(jnp.asarray(np.concatenate(
                [np.asarray(t, np.int32), np.zeros(pad, np.int32)]))
                for t in (rows, cols, served)) + (valid,))
        for layer, kind in enumerate(a.kinds):
            p = layer_weights(cj, seed, layer, kind)
            xs = [block(x, p, a, kind, dm) for x in xs]
        out = [np.asarray(reference._gaps(x, r, c, s, emb, final_scale, dm))
               [v] for x, (r, c, s, v) in zip(xs, picks)]
    return np.concatenate(out)


# -- counts ---------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Shape(flops.Shape):
    """`flops.Shape` of the hybrid: `layers` Mamba-2 or attention mixers,
    each with the held share of a routed MoE and a shared expert.
    Expert work is an expectation: a token's top-k fall on held experts
    k * held / routed times on average."""
    attn_layers: int = 0
    routed: int = 0
    shared_ff: int = 0
    heads: int = 0
    head_dim: int = 0
    d_state: int = 0
    groups: int = 0
    d_conv: int = 0
    state_bytes: int = 4        # float32 recurrent state

    @classmethod
    def of(cls, cj: dict) -> "Shape":
        a = dims(cj)
        return cls(layers=len(a.kinds), d=a.d, hq=a.hq, hkv=a.hkv, hd=a.hd,
                   ff=a.ff, vocab=a.vocab, experts=a.experts, top_k=a.top_k,
                   attn_layers=a.kinds.count("attention"), routed=a.routed,
                   shared_ff=a.shared_ff, heads=a.heads,
                   head_dim=a.head_dim, d_state=a.d_state, groups=a.groups,
                   d_conv=a.d_conv)

    @property
    def mamba_layers(self) -> int:
        return self.layers - self.attn_layers

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv(self) -> int:
        return self.d_inner + 2 * self.groups * self.d_state

    def moe_params(self) -> float:
        """Weights one token multiplies by in one layer's MoE: the router,
        the expected held experts, the shared expert."""
        held = self.top_k * self.experts / self.routed
        return (self.d * self.routed + held * 3 * self.d * self.ff
                + 3 * self.d * self.shared_ff)

    def mamba_params(self) -> int:
        """in_proj, the conv and out_proj."""
        return (self.d * (self.d_inner + self.conv + self.heads)
                + self.conv * self.d_conv + self.d_inner * self.d)

    def attn_params(self) -> int:
        return self.d * self.hd * (2 * self.hq + 2 * self.hkv)

    def ssm_flops(self) -> int:
        """One token's state update and output, one Mamba layer:
        exp(dt A) h + dt x B^T (3 an element) and C . h (2)."""
        return 5 * self.heads * self.head_dim * self.d_state

    def token_flops(self) -> float:
        """A token through every layer, attention over context aside."""
        return (2 * (self.layers * self.moe_params()
                     + self.mamba_layers * self.mamba_params()
                     + self.attn_layers * self.attn_params())
                + self.mamba_layers * self.ssm_flops())

    def prefill_flops(self, prompt: int) -> float:
        causal_keys = prompt * (prompt + 1) // 2
        return (prompt * self.token_flops()
                + self.attn_layers * self.attn_flops(causal_keys)
                + 2 * self.d * self.vocab)

    def decode_flops(self, keys: int) -> float:
        return (self.token_flops() + self.attn_layers * self.attn_flops(keys)
                + 2 * self.d * self.vocab)

    def decode_attn_bytes(self, keys: int) -> int:
        return self.attn_layers * self.paged_attn_bytes(keys)

    def decode_attn_flops(self, keys: int) -> int:
        return self.attn_layers * self.attn_flops(keys)

    def ssm_decode_bytes(self) -> int:
        """HBM bytes one decode row's state updates need over every Mamba
        layer: the float32 state read and written, the conv tail read and
        written, and x, B, C, dt and y."""
        state = self.heads * self.head_dim * self.d_state * self.state_bytes
        tail = (self.d_conv - 1) * self.conv * 2
        vectors = 4 * (2 * self.d_inner + 2 * self.groups * self.d_state
                       + self.heads)
        return self.mamba_layers * (2 * state + 2 * tail + vectors)

    def ssm_decode_flops(self) -> int:
        return self.mamba_layers * self.ssm_flops()


def shape(cj: dict) -> Shape:
    return Shape.of(cj)
