"""Compile the live tier's kernels and the granite decode step for a
described TPU v5e, with no chip attached. Mosaic and XLA:TPU refuse here
what they would refuse on the chip (misaligned blocks, VMEM overuse), so
these guard the served path at no chip time. Nothing runs.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers import every
test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:             # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """ShapeDtypeStructs of `tree` placed on the described chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("B,Hq,Hkv,D,P,page,maxp", [
    (32, 16, 8, 64, 4096, 16, 64),       # granite-moe-1b-a400m
    (8, 32, 8, 128, 1024, 16, 32),       # a D-128 GQA width
    (64, 16, 8, 64, 4097, 16, 64),       # the benchmark's 64 slots
    (16, 40, 10, 128, 1025, 16, 64),     # a phi3-medium stage, 16 slots
])
def test_paged_attention_kernel_compiles(one_chip, B, Hq, Hkv, D, P, page,
                                         maxp):
    from repro.kernels.paged_attention.kernel import paged_attention_kernel
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((B, Hq, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((Hkv, P, page, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((Hkv, P, page, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, maxp), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32)))
    compiled = paged_attention_kernel.lower(*args).compile()
    # one Mosaic call, named as the roofline finds it in a trace
    calls = re.findall(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"',
                       compiled.as_text())
    assert len(calls) == 1 and calls[0].startswith("paged_attention")


def test_flash_attention_kernel_compiles(one_chip):
    from repro.kernels.flash_attention.kernel import flash_attention_kernel
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((2, 16, 512, 64), jnp.bfloat16),
        jax.ShapeDtypeStruct((2, 8, 512, 64), jnp.bfloat16),
        jax.ShapeDtypeStruct((2, 8, 512, 64), jnp.bfloat16)))
    compiled = flash_attention_kernel.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kernels_compile_with_x64_enabled(one_chip):
    """The compiled fleet turns x64 on process-wide; Mosaic has no 64-bit
    types, so the kernels must still trace in 32-bit mode after that."""
    from repro.kernels.flash_attention.kernel import flash_attention_kernel
    from repro.kernels.paged_attention.kernel import paged_attention_kernel
    bf16 = jnp.bfloat16
    paged = _on(one_chip, (
        jax.ShapeDtypeStruct((32, 16, 64), bf16),
        jax.ShapeDtypeStruct((8, 4096, 16, 64), bf16),
        jax.ShapeDtypeStruct((8, 4096, 16, 64), bf16),
        jax.ShapeDtypeStruct((32, 64), jnp.int32),
        jax.ShapeDtypeStruct((32,), jnp.int32)))
    flash = _on(one_chip, (
        jax.ShapeDtypeStruct((2, 16, 512, 64), bf16),
        jax.ShapeDtypeStruct((2, 8, 512, 64), bf16),
        jax.ShapeDtypeStruct((2, 8, 512, 64), bf16)))
    with jax.enable_x64(True):
        for kernel, args in ((paged_attention_kernel, paged),
                             (flash_attention_kernel, flash)):
            compiled = kernel.lower(*args).compile()
            assert "tpu_custom_call" in compiled.as_text()


def test_granite_decode_step_compiles_with_paged_kernel(one_chip):
    """The whole served decode step at granite's published widths and the
    live tier's sizes (launch/serve.py TIER_MAX_BATCH slots, 64 pages
    each plus the trash page, as `build_engine` sizes the pool)."""
    from repro.configs import get_config
    from repro.launch.serve import PAGE_SIZE, TIER_MAX_BATCH
    from repro.models import init_params
    from repro.serving.runner import init_pools, make_step_fns

    cfg = get_config("granite-moe-1b-a400m")
    max_batch, page, maxp = TIER_MAX_BATCH["real"], PAGE_SIZE, 64
    num_pages = max_batch * maxp + 1
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    pools = jax.eval_shape(
        lambda: init_pools(cfg, num_pages, page, max_batch))
    args = _on(one_chip, (
        params, pools,
        jax.ShapeDtypeStruct((max_batch, maxp), jnp.int32),
        jax.ShapeDtypeStruct((max_batch,), jnp.int32),
        jax.ShapeDtypeStruct((max_batch,), jnp.int32),
        jax.ShapeDtypeStruct((max_batch,), jnp.bool_)))
    _, decode_fn = make_step_fns(cfg, page, use_kernel=True)
    compiled = decode_fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B", [1, 30])
def test_ssm_decode_kernel_compiles(one_chip, B):
    """The Mamba-2 state update at granite-4.0-h-small's widths (128
    heads of 64, state 128, one group), one Mosaic call under its name."""
    from repro.kernels.ssm_decode.kernel import ssm_state_update_kernel
    f32 = jnp.float32
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((B, 64, 128, 128), f32),
        jax.ShapeDtypeStruct((B, 128, 64), f32),
        jax.ShapeDtypeStruct((B, 128), f32),
        jax.ShapeDtypeStruct((128,), f32),
        jax.ShapeDtypeStruct((B, 1, 128), f32),
        jax.ShapeDtypeStruct((B, 1, 128), f32),
        jax.ShapeDtypeStruct((128,), f32)))
    compiled = ssm_state_update_kernel.lower(*args).compile()
    calls = re.findall(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"',
                       compiled.as_text())
    assert len(calls) == 1 and calls[0].startswith("ssm_state_update")


def test_granite_hybrid_decode_step_compiles(one_chip):
    """granite-4.0-h-small's served decode step at its benchmark slots,
    with the paged kernel and the state-update kernel in the program."""
    import json
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from chipbench import arch, run
    from repro.serving.runner import init_pools, make_step_fns

    cj = json.loads((root / "chipbench/configs/"
                     "granite-4.0-h-small-pp4-ep4.json").read_text())
    cfg = run.model_config(cj)
    e = cj["engine"]
    B, P, page = e["max_batch"], e["max_pages_per_seq"], e["page_size"]
    params = jax.eval_shape(lambda: arch.resolve(cj).program_params(cj, 0))
    pools = jax.eval_shape(lambda: init_pools(cfg, B * P + 1, page, B))
    args = _on(one_chip, (
        params, pools,
        jax.ShapeDtypeStruct((B, P), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.bool_)))
    _, decode_fn = make_step_fns(cfg, page, use_kernel=True)
    text = decode_fn.lower(*args).compile().as_text()
    calls = re.findall(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"',
                       text)
    assert any(c.startswith("ssm_state_update") for c in calls)
    assert any(c.startswith("paged_attention") for c in calls)


@pytest.mark.parametrize("name", ["granite-moe-1b-a400m",
                                  "phi3-medium-14b-pp4",
                                  "granite-4.0-h-small-pp4-ep4"])
def test_one_row_prefill_compiles(one_chip, name):
    """The prefill a served request runs: one row at the longest padded
    prompt the benchmark's mix forms (960 tokens), written into its own
    slot, against each benchmark configuration's whole pool."""
    import json
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from chipbench import arch, run
    from repro.serving.runner import init_pools, make_step_fns

    cj = json.loads((root / f"chipbench/configs/{name}.json").read_text())
    cfg = run.model_config(cj)
    e = cj["engine"]
    B, P, page = e["max_batch"], e["max_pages_per_seq"], e["page_size"]
    params = jax.eval_shape(lambda: arch.resolve(cj).program_params(cj, 0))
    pools = jax.eval_shape(lambda: init_pools(cfg, B * P + 1, page, B))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    args = _on(one_chip, (params, pools, i32(1, P), i32(B), i32(1, 960),
                          i32(1), jax.ShapeDtypeStruct((1,), jnp.bool_),
                          i32(1)))
    prefill_fn, _ = make_step_fns(cfg, page, use_kernel=True)
    compiled = prefill_fn.lower(*args).compile()
    out = jax.eval_shape(prefill_fn, *args)
    assert out[0].shape == (1,) and out[2].shape == (B,)
    assert compiled.memory_analysis().temp_size_in_bytes > 0
