"""The live tier's device-facing pieces on the CPU: pricing by device
kind, the compile-cache rule, `build_engine` at an explicit config, and
`chip_smoke.py`'s body at a reduced config (kernel checks in Pallas
interpret mode)."""
import importlib.util
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from repro.configs import reduced
from repro.launch import compile_cache
from repro.launch.serve import build_engine, tier_price
from repro.simulate import HW_BY_DEVICE_KIND, V5E, hw_for_device_kind

REPO = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("kind", sorted(HW_BY_DEVICE_KIND))
def test_device_kind_table_prices_known_kinds(kind):
    hw = hw_for_device_kind(kind)
    assert hw is HW_BY_DEVICE_KIND[kind]
    assert hw.name.startswith("tpu-") and hw.price_per_chip_hr > 0


def test_device_kind_table_v5e():
    assert hw_for_device_kind("TPU v5 lite") is V5E


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "tpu-v5e", ""])
def test_device_kind_table_rejects_unknown_kinds(kind):
    with pytest.raises(KeyError):
        hw_for_device_kind(kind)


def test_real_tier_price_never_defaults_on_cpu():
    """The real tier prices by the device it runs on: a CPU has no
    entry, so pricing raises instead of falling back to a flat rate."""
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(KeyError):
        tier_price("real")
    assert tier_price("sim", "tpu-v5e", 4) == pytest.approx(
        4 * V5E.price_per_chip_hr)


def test_build_engine_real_serves_the_given_config():
    cfg = reduced("granite-moe-1b-a400m")
    eng = build_engine(cfg, "real", max_batch=2, max_pages_per_seq=8)
    assert eng.ex.cfg is cfg
    # every slot's longest request plus the trash page, and no more
    assert (eng.cfg.max_batch, eng.cfg.num_pages) == (2, 2 * 8 + 1)
    assert eng.ex.pools[0]["k"].shape[2] == 2 * 8 + 1
    with pytest.raises(ValueError):
        build_engine(cfg, "real", quant="int8", max_batch=2)


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_var_wins(monkeypatch, tmp_path,
                                    restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_defaults_to_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.REPO_CACHE_DIR == REPO / ".jax_cache"
    assert compile_cache.use_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")


_COMPILE_ONE = """
import sys, jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
d = use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * float(sys.argv[1])).lower(
    jnp.ones(7)).compile()
print(d)
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_entries_land_in_chosen_dir(env_set, tmp_path):
    """A compile in a fresh process writes its cache entry where the
    rule says: the env var's directory, else `<repo>/.jax_cache`."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    want = tmp_path / "cache" if env_set else REPO / ".jax_cache"
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    before = set(want.iterdir()) if want.exists() else set()
    # a constant no earlier run compiled, so the cache key is new
    out = subprocess.run(
        [sys.executable, "-c", _COMPILE_ONE, str(time.time_ns() % 10**9)],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == str(want)
    assert set(want.iterdir()) - before, "no cache entry was written"


def test_chip_smoke_body_at_reduced_config(monkeypatch):
    from repro.kernels.paged_attention import kernel, ops
    # the smoke calls the Pallas kernel by name; on the CPU it can only
    # run its body in interpret mode
    def interpreted(*args, interpret=False):
        return kernel.paged_attention_kernel(*args, interpret=True)
    monkeypatch.setattr(ops, "paged_attention_kernel", interpreted)
    chip_smoke = _load_chip_smoke()
    cfg = reduced("granite-moe-1b-a400m")
    lines = []
    live = chip_smoke.run_live(
        cfg, price_per_hr=1.0, n_requests=6, lam=50.0, scale=0.02,
        max_batch=4, max_pages_per_seq=8, log=lines.append)
    assert live["requests"] == 6
    assert live["compiles_in_window"] == 0
    assert not live["decode_kernel"]        # CPU serves the jnp reference
    assert live["kernel_err"] <= chip_smoke.KERNEL_ATOL
    assert live["logit_err"] <= chip_smoke.LOGIT_RTOL
    assert live["c_eff"] > 0
    assert all(live[f"{m}_ms_{path}"] > 0 for m in ("decode", "attn")
               for path in ("kernel", "reference"))
    assert any(s.startswith("compilations inside the window: 0")
               for s in lines)

    sim = chip_smoke.run_sim(lams=(1.0,), log=lines.append)
    assert sim["routes"] == {"compiled": sim["lanes"], "numpy": 0,
                             "fallback": 0}
    assert sim["lanes"] > 0


def test_chip_smoke_check_raises():
    chip_smoke = _load_chip_smoke()
    with pytest.raises(chip_smoke.Failed):
        chip_smoke._check(False, "deliberate")


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_tpu(alone, tmp_path):
    """No accelerator, or no repo beside the script: a non-zero exit and
    no result line."""
    script = REPO / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run([sys.executable, str(script)], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
