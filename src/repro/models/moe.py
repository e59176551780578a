"""Mixture-of-Experts with capacity-grouped einsum dispatch (Mesh-TF style).

Tokens are processed in groups; each token picks top-k experts; each expert
accepts at most `capacity` tokens per group (overflow dropped, standard for
TPU MoE). Dispatch/combine are one-hot einsums so that, with the expert axis
sharded over `model` (EP), XLA emits all-to-all on the group<->expert
resharding boundary — the paper's MoE cost behaviour (§3.3, §5.2) then shows
up directly in the roofline's collective term.

Expert share (`MoEConfig.router_experts`, `expert_offset`): the router
scores all `routed` experts and the top-k softmax runs over them, but the
layer holds only `num_experts` of them, from `expert_offset` on, and
dispatches to and combines only those: its output is the held share's
part of the routed sum (plus the shared expert, which every share
computes). With neither set the layer holds every expert it routes to.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models.layers import dense_init
from repro.parallel.sharding import constrain
from repro.quant import linear


def init_moe(key, d: int, cfg: MoEConfig, mlp_kind: str, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 5)
    E, ff = cfg.num_experts, cfg.expert_ff
    std = 0.02
    def w(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
    p = {
        "router": w(ks[0], (d, cfg.routed)).astype(jnp.float32),
        "experts_up": w(ks[1], (E, d, ff)),
        "experts_down": w(ks[2], (E, ff, d)),
    }
    if mlp_kind == "swiglu":
        p["experts_gate"] = w(ks[3], (E, d, ff))
    if cfg.shared_expert_ff:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(ks[4], d, cfg.shared_expert_ff, mlp_kind, dtype)
    return p


def _w(wp, dtype):
    """Materialize a (possibly quantized) expert weight for the einsum path."""
    if isinstance(wp, dict):
        q, s = wp["q"], wp["scale"]
        if s is None:
            return q.astype(dtype)
        return (q.astype(jnp.bfloat16) * s.astype(jnp.bfloat16)).astype(dtype)
    return wp


def _activate(h_up, h_gate, kind: str):
    if kind == "swiglu":
        return jax.nn.silu(h_gate) * h_up
    if kind == "relu2":
        return jnp.square(jax.nn.relu(h_up))
    return jax.nn.gelu(h_up, approximate=True)


def apply_moe(p, x: jnp.ndarray, cfg: MoEConfig, mlp_kind: str,
              qcfg=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Dispatch pipeline: group tokens -> route top-k -> positional cumsum for
    capacity -> one-hot dispatch einsum -> expert MLPs (batched over E) ->
    combine einsum weighted by gate probs. Each stage runs under its
    named scope (`route`, `dispatch`, `experts`, `combine`), so a profile
    attributes device time to it.
    """
    B, S, d = x.shape
    E, K, Er = cfg.num_experts, cfg.top_k, cfg.routed
    T = B * S
    Sg = min(cfg.group_size, T)
    G = -(-T // Sg)
    cap = max(K, int(math.ceil(Sg * K / E * cfg.capacity_factor)))

    # a last, partial group is filled with zero tokens; they follow the
    # real ones in every expert's queue, so they take no slot from them
    pad = G * Sg - T
    xg = (jnp.pad(x.reshape(T, d), ((0, pad), (0, 0))) if pad
          else x).reshape(G, Sg, d)
    with jax.named_scope("route"):
        logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32),
                            p["router"])
        probs = jax.nn.softmax(logits, axis=-1)                # (G,Sg,Er)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)          # (G,Sg,K)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

        # Load-balancing aux loss (Switch-style):
        # E * mean(frac_tokens * mean_prob)
        frac = jnp.mean(
            jax.nn.one_hot(gate_idx[..., 0], Er, dtype=jnp.float32),
            axis=(0, 1))
        aux = Er * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))

        if cfg.expert_offset:
            gate_idx = gate_idx - cfg.expert_offset
        # experts not held here (index outside [0, E)) get no slot
        onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (G,Sg,K,E)
        # position of each (token, k) assignment within its expert's queue
        flat = onehot.reshape(G, Sg * K, E)
        pos = jnp.cumsum(flat, axis=1) - 1.0                   # (G,Sg*K,E)
        pos = pos.reshape(G, Sg, K, E)
        keep = (pos < cap) & (onehot > 0)
        pos_c = jnp.clip(pos, 0, cap - 1).astype(jnp.int32)
        cap_oh = jax.nn.one_hot(pos_c, cap, dtype=jnp.float32)  # (G,Sg,K,E,C)
        dispatch = jnp.where(keep[..., None], cap_oh, 0.0)     # (G,Sg,K,E,C)
        combine = dispatch * gate_vals[..., None, None]
        dispatch_t = jnp.sum(dispatch, axis=2)                 # (G,Sg,E,C)
        combine_t = jnp.sum(combine, axis=2)

    cdt = x.dtype
    with jax.named_scope("dispatch"):
        expert_in = jnp.einsum("gsec,gsd->egcd", dispatch_t.astype(cdt), xg)
        # e over EP where divisible AND g keeps the batch axes: when the
        # expert count doesn't divide the model axis (mixtral: 8 on 16) the
        # e-spec drops but g-sharding prevents GSPMD replicating a multi-GB
        # tensor (observed: 5.7 TB/step of all-reduce before this
        # constraint carried the batch dim — §Perf log, mixtral train
        # baseline-fix)
        expert_in = constrain(expert_in, "experts", "batch", None, None)

    with jax.named_scope("experts"):
        up = jnp.einsum("egcd,edf->egcf", expert_in,
                        _w(p["experts_up"], cdt))
        gatep = p.get("experts_gate")
        gate_h = (jnp.einsum("egcd,edf->egcf", expert_in, _w(gatep, cdt))
                  if gatep is not None else None)
        h = _activate(up, gate_h, mlp_kind)
        out_e = jnp.einsum("egcf,efd->egcd", h, _w(p["experts_down"], cdt))
        out_e = constrain(out_e, "experts", "batch", None, None)

    with jax.named_scope("combine"):
        out = jnp.einsum("gsec,egcd->gsd", combine_t.astype(cdt), out_e)
        out = (out.reshape(G * Sg, d)[:T] if pad else out).reshape(B, S, d)
    if "shared" in p:
        from repro.models.layers import apply_mlp
        with jax.named_scope("shared"):
            out = out + apply_mlp(p["shared"], x, mlp_kind, qcfg)
    return out, aux
