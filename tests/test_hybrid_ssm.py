"""The hybrid SSM path at CPU sizes: Mamba-2's chunked SSD against its
sequential recurrence, the fused decode state update against its
reference, the expert share against the uncut MoE layer, prefill state
at each row's own length (Mamba-1 and Mamba-2), served prefill then
decode against the float32 reference on logits, and the benchmark's
other two configurations' step programs pinned by hash."""
import dataclasses
import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced
from repro.configs.base import MoEConfig
from repro.kernels.ssm_decode.ops import ssm_state_update
from repro.models import init_params
from repro.models import ssm as ssm_lib
from repro.models.layers import apply_mlp
from repro.models.moe import apply_moe, init_moe
from repro.serving.runner import init_pools, make_decode_logits, make_step_fns

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
SEED = 2**31 + 17


def _ssd_inputs(key, B=2, S=45, H=8, P=4, G=2, N=8):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 2.0)
    Bm = jax.random.normal(ks[2], (B, S, G, N))
    Cm = jax.random.normal(ks[3], (B, S, G, N))
    A = -jnp.arange(1, H + 1, dtype=jnp.float32) / 4
    return x, dt, Bm, Cm, A


@pytest.mark.parametrize("chunk", [1, 8, 16, 45, 64])
def test_ssd_chunked_matches_sequential(chunk):
    """Every chunk length, one that does not divide S among them, gives
    the sequential recurrence's outputs and final state. In float32 the
    only difference is the order of summation: 1e-4 relative."""
    x, dt, Bm, Cm, A = _ssd_inputs(jax.random.PRNGKey(0))
    y, h = ssm_lib.ssd(x, dt, Bm, Cm, A, chunk)
    yr, hr = ssm_lib.ssd_ref(x, dt, Bm, Cm, A)
    np.testing.assert_allclose(y, yr, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h, hr, rtol=1e-4, atol=1e-4)


def test_ssd_zero_dt_leaves_the_state():
    """dt = 0 past a length: the state is the one at that length."""
    x, dt, Bm, Cm, A = _ssd_inputs(jax.random.PRNGKey(1))
    n = 30
    _, h_short = ssm_lib.ssd_ref(x[:, :n], dt[:, :n], Bm[:, :n], Cm[:, :n], A)
    masked = dt.at[:, n:].set(0.0)
    _, h = ssm_lib.ssd(x, masked, Bm, Cm, A, 16)
    np.testing.assert_allclose(h, h_short, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,H,P,N,G", [
    (3, 8, 16, 32, 1),      # 8 heads packed to a 128-lane row
    (2, 16, 64, 128, 1),    # granite-4.0-h-small's head and state sizes
    (2, 16, 32, 16, 2),     # two groups of B and C
])
def test_ssm_decode_kernel_matches_ref(B, H, P, N, G):
    """The Pallas kernel, interpreted, against ref.py and the unpacked
    formula: both are float32 on the VPU, so 1e-5."""
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    h = jax.random.normal(ks[0], (B, H, P, N))
    x = jax.random.normal(ks[1], (B, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, H)))
    A = -jnp.arange(1, H + 1, dtype=jnp.float32)
    D = jax.random.normal(ks[3], (H,))
    Bm = jax.random.normal(ks[4], (B, G, N))
    Cm = jax.random.normal(ks[5], (B, G, N))
    cfg = dataclasses.replace(ssm_lib.SSMConfig(), kind="mamba2",
                              n_heads=H, head_dim=P, n_groups=G, d_state=N)
    k = ssm_lib.state_pack(cfg)
    hp = ssm_lib.pack_state(h, k)
    y_ref, h_ref = ssm_state_update(hp, x, dt, A, Bm, Cm, D, use_kernel=False)
    y_k, h_k = ssm_state_update(hp, x, dt, A, Bm, Cm, D, use_kernel=True,
                                interpret=True)
    np.testing.assert_allclose(y_k, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h_k, h_ref, rtol=1e-5, atol=1e-5)
    rep = lambda t: jnp.repeat(t, H // G, axis=1)  # noqa: E731
    hn = (jnp.exp(dt * A)[..., None, None] * h
          + (dt[..., None] * x)[..., None] * rep(Bm)[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", hn, rep(Cm),
                   precision="highest") + D[:, None] * x
    np.testing.assert_allclose(ssm_lib.unpack_state(h_ref, k), hn,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y_ref, y, rtol=1e-5, atol=1e-5)


def test_ssm_decode_zero_dt_keeps_the_state():
    """An inactive row's dt is 0: its state comes back bit for bit."""
    B, H, P, N = 2, 8, 16, 32
    hp = jax.random.normal(jax.random.PRNGKey(3), (B, 1, N, H * P))
    _, h = ssm_state_update(hp, jnp.ones((B, H, P)), jnp.zeros((B, H)),
                            -jnp.ones(H), jnp.ones((B, 1, N)),
                            jnp.ones((B, 1, N)), jnp.ones(H),
                            use_kernel=True, interpret=True)
    assert bool(jnp.all(h == hp))


def test_expert_shares_sum_to_the_uncut_layer():
    """granite-4.0-h-small's routing (72 experts, top-10, a shared expert)
    at a small width: four shares of 18 experts, each routing over all
    72, add up, with the shared expert counted once, to the layer that
    holds all 72. Float32 throughout, dropless (group = top-k): 1e-5."""
    d, ff, sff = 32, 16, 24
    full = MoEConfig(num_experts=72, top_k=10, expert_ff=ff, group_size=10,
                     shared_expert_ff=sff)
    p = init_moe(jax.random.PRNGKey(4), d, full, "swiglu", jnp.float32)
    p = jax.tree.map(lambda t: t * 20, p)      # a router that discriminates
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 10, d))
    want, _ = apply_moe(p, x, full, "swiglu")
    shared = apply_mlp(p["shared"], x, "swiglu")
    total = shared
    for i in range(4):
        cfg = dataclasses.replace(full, num_experts=18, router_experts=72,
                                  expert_offset=18 * i)
        share = dict(p, **{k: p[k][18 * i:18 * (i + 1)] for k in
                           ("experts_up", "experts_down", "experts_gate")})
        out, _ = apply_moe(share, x, cfg, "swiglu")
        total = total + (out - shared)
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(want - shared).max()) > 1e-2   # experts do count


def _prefill_state(cfg, params, lens, Lp, rows):
    """Mamba pools after one prefill of the `rows` of a right-padded batch."""
    B, P, page = len(lens), 4, 16
    pools = init_pools(cfg, B * P + 1, page, B)
    tokens = (np.arange(B)[:, None] * 101 + np.arange(Lp)[None] * 7 + 3
              ) % cfg.vocab_size
    tokens = np.where(np.arange(Lp)[None] < np.asarray(lens)[:, None],
                      tokens, 0).astype(np.int32)
    prefill_fn, _ = make_step_fns(cfg, page)
    do = np.isin(np.arange(B), rows)
    bt = jnp.asarray(1 + np.arange(B * P).reshape(B, P), jnp.int32)
    _, pools, _ = prefill_fn(params, pools, bt, jnp.zeros(B, jnp.int32),
                             jnp.asarray(tokens), jnp.asarray(lens, jnp.int32),
                             jnp.asarray(do))
    return [pl for pl in pools if "h" in pl]


def _mamba1_hybrid():
    cfg = reduced("jamba-v0.1-52b")
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, group_size=4, capacity_factor=4.0))     # dropless


@pytest.mark.parametrize("which", ["mamba1", "mamba2"])
def test_prefill_state_is_at_each_rows_length(which):
    """Rows of unequal lengths prefilled together, right-padded to one
    length: each row's SSM state and conv tail are those of the row
    prefilled alone at exactly its length. (The state used to run on
    through the pad tokens.)"""
    if which == "mamba1":
        cfg = _mamba1_hybrid()
    else:
        from chipbench import run
        cj = json.loads((ROOT / "chipbench/testdata/"
                         "tiny-granite-hybrid.json").read_text())
        cfg = run.model_config(cj)
    params = jax.tree.map(lambda t: t.astype(jnp.float32)
                          if t.dtype == jnp.bfloat16 else t,
                          init_params(jax.random.PRNGKey(6), cfg))
    cfg = dataclasses.replace(cfg, dtype="float32")
    lens = [3, 20, 9, 32]
    together = _prefill_state(cfg, params, lens, 32, [0, 1, 2, 3])
    for row, n in enumerate(lens):
        alone = _prefill_state(cfg, params, [n] * 4, n, [row])
        for a, b in zip(together, alone):
            for name in ("h", "conv"):
                np.testing.assert_allclose(a[name][:, row], b[name][:, row],
                                           rtol=1e-4, atol=1e-5)


# -- served prefill then decode against the reference, on logits -----------

def _ref_logits(cj, mod, ids):
    from chipbench import reference, weights
    a, dm = mod.dims(cj), reference.dims(cj)
    m = dict(dm)
    emb = weights.published(cj, "embed",
                            weights.embed_weights(cj, SEED)["embed"])
    scale = weights.published(cj, "final_norm", jnp.float32(1))
    with jax.default_matmul_precision("highest"):
        x = m["m_embedding"] * emb[jnp.asarray(ids)]
        for layer, kind in enumerate(a.kinds):
            x = mod.block(x, mod.layer_weights(cj, SEED, layer, kind), a,
                          kind, dm)
        return np.asarray(reference._rms(x, m["eps"]) * scale @ emb.T
                          / m["m_logits"])


# The program served in float32 (weights, activations, K/V and conv
# tails) differs from the float32 reference only in the order of its sums:
# at most 8.4e-6 over 60 decode steps of logits whose spread is 0.48. A
# state rounded to bfloat16 each step, which accumulates, reads 7.2e-3.
LOGIT_TOL = 1e-4


@pytest.mark.parametrize("state", ["float32", "bfloat16"])
def test_served_hybrid_logits_match_reference(state):
    from chipbench import arch, run
    cj = json.loads((ROOT / "chipbench/testdata/tiny-granite-hybrid.json")
                    .read_text())
    mod = arch.resolve(cj)
    cfg = dataclasses.replace(run.model_config(cj), dtype="float32")
    params = jax.tree.map(lambda t: t.astype(jnp.float32)
                          if t.dtype == jnp.bfloat16 else t,
                          mod.program_params(cj, SEED))
    B, P, page, Lp, steps = 4, 8, 16, 40, 60
    lens = np.array([5, 17, 40, 9], np.int32)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cj["vocab_size"], (B, Lp + steps)).astype(np.int32)
    pools = init_pools(cfg, B * P + 1, page, B)
    bt = jnp.asarray(1 + np.arange(B * P).reshape(B, P), jnp.int32)
    prefill_fn, _ = make_step_fns(cfg, page)
    decode = jax.jit(make_decode_logits(cfg, page))
    prompt = np.where(np.arange(Lp)[None] < lens[:, None], toks[:, :Lp], 0)
    _, pools, sl = prefill_fn(params, pools, bt, jnp.zeros(B, jnp.int32),
                              jnp.asarray(prompt), jnp.asarray(lens),
                              jnp.ones(B, bool))
    got = []
    for t in range(steps):
        logits, pools, sl = decode(params, pools, bt, sl,
                                   jnp.asarray(toks[:, Lp + t]),
                                   jnp.ones(B, bool))
        if state == "bfloat16":
            pools = [dict(pl, h=pl["h"].astype(jnp.bfloat16)
                          .astype(jnp.float32)) if "h" in pl else pl
                     for pl in pools]
        got.append(np.asarray(logits))
    ids = np.zeros((B, Lp + steps), np.int32)
    for b in range(B):
        seq = np.concatenate([toks[b, :lens[b]], toks[b, Lp:]])
        ids[b, :len(seq)] = seq
    ref = _ref_logits(cj, mod, ids)
    want = np.stack([ref[b, lens[b]:lens[b] + steps] for b in range(B)], 1)
    err = float(np.abs(np.stack(got) - want).max())
    assert (err <= LOGIT_TOL) == (state == "float32"), err


# -- the benchmark's other configurations -----------------------------------

# sha256 of the lowered (StableHLO) step programs at each configuration's
# abstract shapes, the full-batch prefill at 64 tokens. The decode hashes
# are as the code before the hybrid path and the expert share lowered
# them; the prefill hashes as the prefill lowers them whose layer scan
# carries only the rows' activations and which writes K/V (one pass over
# the pool) and states once, after the scan
LOWERED = {
    ("granite-moe-1b-a400m", "decode"):
        "01081c1eb59ea848f213342b0df461e741ecf97a2f130aab27d7474f3900a410",
    ("granite-moe-1b-a400m", "prefill"):
        "1785178f70c9f5b7fc7082022cd23aa204c4f4adcbc9e1c7babea65ada173833",
    ("phi3-medium-14b-pp4", "decode"):
        "a601fd76a23ec29540f64ba3ceb642b130dc86453ff6221a28015b7721cc53e7",
    ("phi3-medium-14b-pp4", "prefill"):
        "18c60ca4c7710b51f695ad2f9e626dac1e60c4ec400a9aa6a56a5be28ab84d33",
}


@pytest.mark.parametrize("name,step", sorted(LOWERED))
def test_benchmark_step_programs_unchanged(name, step):
    from chipbench import arch, run
    cj = json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())
    cfg = run.model_config(cj)
    e = cj["engine"]
    B, P, page = e["max_batch"], e["max_pages_per_seq"], e["page_size"]
    params = jax.eval_shape(lambda: arch.resolve(cj).program_params(cj, 0))
    pools = jax.eval_shape(lambda: init_pools(cfg, B * P + 1, page, B))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    active = jax.ShapeDtypeStruct((B,), jnp.bool_)
    prefill_fn, decode_fn = make_step_fns(cfg, page)
    if step == "decode":
        lowered = decode_fn.lower(params, pools, i32(B, P), i32(B), i32(B),
                                  active)
    else:
        lowered = prefill_fn.lower(params, pools, i32(B, P), i32(B),
                                   i32(B, 64), i32(B), active)
    text = lowered.as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED[name, step]
