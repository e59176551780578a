"""granite-4.0-h-small's configuration through the benchmark, on the CPU:
a tiny configuration of its layout (`testdata/tiny-granite-hybrid.json`,
arch `granite-hybrid`) served through `run.build` and checked by
`run.check`; the int8 control and every fault fail the limit. Then the
real configuration's program agrees with its published keys, its weights
with the program's layout, and its counts with hand counts.

The limit is set between the readings of sound runs and of the control
on the CPU (two seeds each): sound 0.0-0.00009, int8 0.0029-0.0040,
faults 0.81-2.16.
"""
import json
from pathlib import Path

import jax
import pytest

from chipbench import arch, control, run
from chipbench.record import Run
from chipbench.trace_reduce import Reduced
from chipbench.wallclock import Step

HERE = Path(__file__).resolve().parent
TESTDATA = HERE / "testdata"
CONFIG = HERE / "configs" / "granite-4.0-h-small-pp4-ep4.json"
SEEDS = [2**31 + 17, 99]
LIMIT = 0.0015


def _tiny() -> dict:
    return json.loads((TESTDATA / "tiny-granite-hybrid.json").read_text())


def _correct(seed, **kw) -> bool:
    cj = _tiny()
    mix = json.loads((TESTDATA / "tiny-backlog.json").read_text())
    cell = {"name": cj["name"], "config": cj["name"],
            "traffic": "tiny-backlog", "chips": 1}
    served = run.serve(cell, cj, mix, run.peaks_for("TPU v5 lite"),
                       seed=seed, seconds=2.0, trace=False,
                       log=lambda m: None, **kw)
    checks, _ = run.check(served, cj, {"mean_logit_gap": {"limit": LIMIT}},
                          seed, log=lambda m: None)
    assert checks["sampled_requests"]["value"] >= 4
    return all(c["ok"] for c in checks.values())


@pytest.mark.parametrize("seed", SEEDS)
def test_tiny_hybrid_served_is_correct(seed):
    assert _correct(seed)


@pytest.mark.parametrize("fault", [None] + sorted(control.FAULTS))
def test_control_and_faults_fail(fault):
    kw = ({"make_engine": control.build_int8} if fault is None
          else {"fault": control.FAULTS[fault]})
    assert not _correct(SEEDS[0], **kw)


def test_program_is_the_published_configuration():
    """What `run.model_config` builds from the file's `program` block is
    what its published keys say."""
    cj = json.loads(CONFIG.read_text())
    cfg = run.model_config(cj)
    s, moe = cfg.ssm, cfg.moe
    assert (s.kind, s.n_heads, s.head_dim, s.d_state, s.n_groups, s.d_conv,
            s.expand, s.norm_eps) == (
        "mamba2", cj["mamba_n_heads"], cj["mamba_d_head"],
        cj["mamba_d_state"], cj["mamba_n_groups"], cj["mamba_d_conv"],
        cj["mamba_expand"], cj["rms_norm_eps"])
    assert moe.routed == cj["published"]["num_local_experts"] == 72
    assert (moe.num_experts, moe.top_k, moe.expert_ff,
            moe.shared_expert_ff) == (18, 10, 768, 1536)
    assert moe.group_size == moe.top_k           # dropless
    kinds = ["attn" if k == "attention" else k
             for k in cj["layer_types"][:cj["num_hidden_layers"]]]
    assert list(cfg.block_pattern()) == kinds
    assert cfg.rope_kind == "none" and cj["position_embedding_type"] == "nope"
    assert cj["engine"]["max_batch"] % moe.group_size == 0


@pytest.mark.parametrize("config", ["tiny", "published"])
def test_weights_match_the_program_layout(config):
    from repro.models import init_params
    cj = _tiny() if config == "tiny" else json.loads(CONFIG.read_text())
    mod = arch.resolve(cj)
    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                              run.model_config(cj)))
    got = jax.eval_shape(lambda: mod.program_params(cj, 0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)


def test_counts_by_hand():
    """About 3.26 B parameters here (9 x 291 M Mamba + MoE layers, 231 M
    for the attention layer, 411 M of embedding); a decode row's state
    updates move 8.56 MB a Mamba layer."""
    cj = json.loads(CONFIG.read_text())
    s = arch.resolve(cj).shape(cj)
    assert (s.layers, s.attn_layers, s.mamba_layers) == (10, 1, 9)
    moe_held = 18 * 3 * 4096 * 768 + 4096 * 72 + 3 * 4096 * 1536
    mamba = 4096 * (8192 + 8448 + 128) + 8448 * 4 + 8192 * 4096
    attn = 4096 * 128 * (2 * 32 + 2 * 8)
    total = 9 * (mamba + moe_held) + attn + moe_held + 100352 * 4096
    assert total == pytest.approx(3.26e9, rel=0.01)
    assert s.mamba_params() == mamba
    assert s.moe_params() == pytest.approx(
        4096 * 72 + 2.5 * 3 * 4096 * 768 + 3 * 4096 * 1536)
    per_layer = (2 * 128 * 64 * 128 * 4 + 2 * 3 * 8448 * 2
                 + 4 * (2 * 8192 + 2 * 128 + 128))
    assert s.ssm_decode_bytes() == 9 * per_layer
    assert s.decode_attn_bytes(100) == s.paged_attn_bytes(100)


def _traced_run(cj, kernel_s: float, rows: int, steps: int) -> Run:
    red = Reduced(window=(0, 2 * 10**9), devices=["/device:TPU:0"],
                  busy_s=1.9, op_s={"jit_decode_fn:ssm_state_update.3":
                                    kernel_s},
                  module_s={}, module_n={}, gaps=[])
    st = [Step("decode", 0.1 * i, 0.1 * i + 0.05, list(range(rows)),
               [300] * rows, [1] * rows) for i in range(1, steps + 1)]
    return Run(cell={"name": "c"}, config=cj, mix={"kind": "backlog"},
               peaks=run.peaks_for("TPU v5 lite"), seconds=2.0,
               setup_s=1.0, w0=0.0, w1=2.0, steps=st, arrival={},
               tokens={}, times={}, trace=red, traced=(0.0, 2.0))


def test_ssm_decode_roofline_reader():
    """The state updates' least time, memory-bound, over the kernel's
    device time; None for a configuration without Mamba-2 layers."""
    cj = json.loads(CONFIG.read_text())
    r = _traced_run(cj, kernel_s=0.05, rows=30, steps=4)
    least = 4 * 30 * r.shape.ssm_decode_bytes() / r.peaks["hbm_bytes_per_s"]
    assert run.reader("ssm_decode_roofline")(r) == pytest.approx(
        100 * least / 0.05)
    granite = json.loads((HERE / "configs/granite-moe-1b-a400m.json")
                         .read_text())
    assert run.reader("ssm_decode_roofline")(
        _traced_run(granite, 0.05, 30, 4)) is None
