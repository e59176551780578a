"""The live tier's own instrumentation on the CPU: the named scopes in the
lowered step programs, the prefill counter on both scheduler paths, the
host-span helper off JAX, and the compile cache's key."""
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced
from repro.launch import compile_cache
from repro.launch.serve import build_engine
from repro.models import init_params
from repro.serving import Engine, EngineConfig
from repro.serving.request import Request
from repro.serving.runner import init_pools, make_step_fns

REPO = Path(__file__).resolve().parents[1]
B, MAXP, PAGE = 2, 8, 16


def _op_names(step: str):
    cfg = reduced("granite-moe-1b-a400m")
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    pools = jax.eval_shape(lambda: init_pools(cfg, B * MAXP + 1, PAGE, B))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    b1 = jax.ShapeDtypeStruct((B,), jnp.bool_)
    prefill_fn, decode_fn = make_step_fns(cfg, PAGE)
    if step == "decode":
        lowered = decode_fn.lower(params, pools, i32(B, MAXP), i32(B),
                                  i32(B), b1)
    else:
        lowered = prefill_fn.lower(params, pools, i32(B, MAXP), i32(B),
                                   i32(B, 64), i32(B), b1)
    return set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_step_programs_carry_the_named_scopes(step):
    names = _op_names(step)
    for scope in ("embed/", "/attention/", "/attention/kv_write/",
                  "/moe/route/", "/moe/dispatch/", "/moe/experts/",
                  "/moe/combine/", "unembed/"):
        assert any(scope in n for n in names), scope
    # the layer stack's ops sit under a layer scope, not beside it
    body = [n for n in names if "/closed_call/" in n]
    assert body and all(re.search(r"/closed_call/(attention|moe)/", n)
                        for n in body)


def _requests():
    rng = np.random.default_rng(0)
    lens = [(20, 3), (50, 2), (100, 4), (30, 2), (70, 1)]
    return [Request(i, 0.0, n, m, prompt=list(rng.integers(0, 256, n)))
            for i, (n, m) in enumerate(lens)]


def test_prefill_counter_counts_the_padded_matrix_on_both_paths():
    """Rows x padded length of every prefill matrix the engine hands the
    executor, the same on the fast and the reference scheduler."""
    ex = build_engine(reduced("granite-moe-1b-a400m"), "real", max_batch=B,
                      max_pages_per_seq=MAXP).ex
    shapes = []
    prefill = ex.prefill

    def recorded(tokens, *args):
        shapes.append(tokens.shape)
        return prefill(tokens, *args)
    ex.prefill = recorded
    counted = {}
    for fast in (True, False):
        shapes.clear()
        eng = Engine(EngineConfig(max_batch=B, page_size=PAGE,
                                  num_pages=B * MAXP + 1,
                                  max_pages_per_seq=MAXP,
                                  fast_forward=fast), ex)
        reqs = _requests()
        eng.run(reqs)
        assert all(len(s) == 2 and s[0] == B and s[1] % 64 == 0
                   for s in shapes)
        computed = eng.metrics.get("repro:prefill_tokens_computed_total")
        assert computed == sum(r * n for r, n in shapes)
        prompt = eng.metrics.get("repro:prompt_tokens_total")
        assert prompt == sum(r.prompt_len for r in reqs) < computed
        counted[fast] = (computed, prompt, list(shapes))
    assert counted[True] == counted[False]


def test_sim_tier_stays_off_jax():
    """The sim tier serves without importing JAX, spans and all."""
    code = """
import sys
from repro.configs import get_config
from repro.serving import Engine, EngineConfig, SimExecutor, synth_requests
from repro.serving import ArrivalSpec
from repro.serving.tracing import span
from repro.simulate import StepTimeModel, V5E
cfg = get_config("llama31-8b")
stm = StepTimeModel(cfg, V5E)
eng = Engine(EngineConfig(max_batch=4), SimExecutor(cfg, stm))
eng.run(synth_requests(ArrivalSpec(lam=5, n_requests=10, seed=0)))
assert span("a") is span("b")
print("jax" in sys.modules, eng.metrics.get("repro:request_success_total"))
"""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["False", "10.0"]


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_key_keeps_the_scopes(env_set, monkeypatch, tmp_path):
    """`use_compile_cache()` keeps metadata, the named scopes among it, in
    the persistent cache's key, with or without the env var."""
    key = "jax_compilation_cache_include_metadata_in_key"
    before = (jax.config.jax_compilation_cache_dir, getattr(jax.config, key))
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update(key, False)
    try:
        compile_cache.use_compile_cache()
        assert getattr(jax.config, key) is True
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update(key, before[1])
