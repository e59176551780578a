"""The prefill of the admitted rows only, at CPU sizes: one-row calls into
their own slots against the full-batch call, an inert call that changes
nothing, and `RealExecutor.prefill` of rows at unequal lengths, then
decode, against the float32 full-sequence forward. Each over an
attention-only, a Mamba-2 hybrid and a Mamba-1 hybrid configuration."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced
from repro.models import forward, init_params
from repro.serving import RealExecutor
from repro.serving.executors import prefill_len
from repro.serving.runner import init_pools, make_decode_logits, make_step_fns

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
CONFIGS = ["attention", "mamba2", "mamba1"]
PAGE = 16
# as tests/test_hybrid_ssm.py: float32 programs differ from a float32
# reference only in the order of their sums
LOGIT_TOL = 1e-4


def _f32(which):
    """A float32 configuration and parameters; every MoE is dropless, so
    grouping tokens differently changes no token's experts."""
    if which == "mamba1":
        cfg = reduced("jamba-v0.1-52b")
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, group_size=4, capacity_factor=4.0))
    else:
        from chipbench import run
        name = "tiny-dense" if which == "attention" else "tiny-granite-hybrid"
        cfg = run.model_config(json.loads(
            (ROOT / f"chipbench/testdata/{name}.json").read_text()))
    params = jax.tree.map(lambda t: t.astype(jnp.float32)
                          if t.dtype == jnp.bfloat16 else t,
                          init_params(jax.random.PRNGKey(3), cfg))
    return dataclasses.replace(cfg, dtype="float32"), params


def _random_pools(cfg, num_pages, B, seed=0):
    """Pools whose every element is set, so that a stray write shows."""
    pools = init_pools(cfg, num_pages, PAGE, B)
    leaves, tree = jax.tree.flatten(pools)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, x.shape).astype(x.dtype)
        for k, x in zip(keys, leaves)])


def _prompts(cfg, lens, Lp):
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size, (len(lens), Lp)).astype(np.int32)
    return np.where(np.arange(Lp)[None] < np.asarray(lens)[:, None], toks, 0)


@pytest.mark.parametrize("which", CONFIGS)
def test_one_row_calls_match_the_full_batch(which):
    """Rows 5 and 2 of 8, prefilled each alone at its own padded length
    into its own slot, leave pages, states, seq_lens and first tokens as
    one full-batch call of the same rows does."""
    cfg, params = _f32(which)
    B, P = 8, 8
    lens = np.zeros(B, np.int32)
    lens[5], lens[2] = 20, 100
    do = lens > 0
    Lp = prefill_len(int(lens.max()))
    tokens = _prompts(cfg, lens, Lp)
    bt = jnp.asarray(1 + np.arange(B * P).reshape(B, P), jnp.int32)
    pools = _random_pools(cfg, B * P + 1, B)
    seq = jnp.asarray(np.arange(B) + 3, jnp.int32)
    prefill_fn, _ = make_step_fns(cfg, PAGE)

    first_all, want, seq_all = prefill_fn(
        params, pools, bt, seq, jnp.asarray(tokens), jnp.asarray(lens),
        jnp.asarray(do))
    got, seq_got = pools, seq
    for slot in (5, 2):
        lp = prefill_len(int(lens[slot]))
        first, got, seq_got = prefill_fn(
            params, got, bt[slot:slot + 1], seq_got,
            jnp.asarray(tokens[slot:slot + 1, :lp]),
            jnp.asarray(lens[slot:slot + 1]), jnp.ones(1, bool),
            jnp.asarray([slot], jnp.int32))
        assert int(first[0]) == int(first_all[slot])
    np.testing.assert_array_equal(seq_got, seq_all)
    assert [int(seq_got[s]) for s in (5, 2)] == [20, 100]
    for a, b, old in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                         jax.tree.leaves(pools)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        assert not np.array_equal(a, old)              # the rows were kept


@pytest.mark.parametrize("which", CONFIGS)
def test_inert_call_changes_nothing(which):
    """An empty admission's call (slot max_batch, nothing flagged) leaves
    every slot's state, seq_lens and every page but the trash page."""
    cfg, params = _f32(which)
    B, P = 4, 8
    pools = _random_pools(cfg, B * P + 1, B, seed=1)
    seq = jnp.asarray([7, 0, 33, 12], jnp.int32)
    prefill_fn, _ = make_step_fns(cfg, PAGE)
    _, after, seq_after = prefill_fn(
        params, pools, jnp.zeros((1, P), jnp.int32), seq,
        jnp.zeros((1, 64), jnp.int32), jnp.zeros(1, jnp.int32),
        jnp.zeros(1, bool), jnp.asarray([B], jnp.int32))
    np.testing.assert_array_equal(seq_after, seq)
    for new, old in zip(after, pools):
        for name in old:
            a, b = np.asarray(new[name]), np.asarray(old[name])
            if name in ("k", "v"):                  # (L, Hkv, P, page, D)
                a, b = a[:, :, 1:], b[:, :, 1:]
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("which", CONFIGS)
def test_executor_prefill_then_decode_matches_reference(which):
    """Two admissions (slots 3 and 1 at 9 and 70 tokens, then slot 0 at
    30) through `RealExecutor.prefill`, then teacher-forced decode of all
    three: first tokens and logits as the full-sequence forward's."""
    cfg, params = _f32(which)
    B, P, steps = 4, 8, 6
    ex = RealExecutor(cfg, params, num_pages=B * P + 1, page_size=PAGE,
                      max_batch=B)
    bt = (1 + np.arange(B * P).reshape(B, P)).astype(np.int32)
    lens = {3: 9, 1: 70, 0: 30}
    rng = np.random.default_rng(2)
    seqs = {s: rng.integers(0, cfg.vocab_size, n + steps).astype(np.int32)
            for s, n in lens.items()}
    firsts = {}
    for admitted in ([3, 1], [0]):
        lp = prefill_len(max(lens[s] for s in admitted))
        tokens = np.zeros((B, lp), np.int32)
        ln, do = np.zeros(B, np.int32), np.zeros(B, bool)
        for s in admitted:
            tokens[s, :lens[s]] = seqs[s][:lens[s]]
            ln[s], do[s] = lens[s], True
        first, dt = ex.prefill(tokens, ln, do, bt)
        assert first.shape == (B,) and dt > 0
        firsts.update({s: int(first[s]) for s in admitted})
    assert [int(x) for x in ex.seq_lens] == [30, 70, 0, 9]

    decode = jax.jit(make_decode_logits(cfg, PAGE))
    active = jnp.asarray(np.isin(np.arange(B), list(lens)))
    pools, sl, got = ex.pools, ex.seq_lens, []
    for t in range(steps):
        tok = np.zeros(B, np.int32)
        for s, n in lens.items():
            tok[s] = seqs[s][n + t]
        logits, pools, sl = decode(params, pools, jnp.asarray(bt), sl,
                                   jnp.asarray(tok), active)
        got.append(np.asarray(logits))
    for s, n in lens.items():
        with jax.default_matmul_precision("highest"):
            ref, _ = forward(params, cfg, {"tokens": jnp.asarray(seqs[s])[None]})
        ref = np.asarray(ref[0])
        assert firsts[s] == int(ref[n - 1].argmax())
        err = float(np.abs(np.stack([g[s] for g in got])
                           - ref[n:n + steps]).max())
        assert err <= LOGIT_TOL, (s, err)
