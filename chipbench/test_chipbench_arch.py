"""How a configuration enters the benchmark, on the CPU at test sizes:
the program's `ModelConfig` built from the file (`run.model_config`), the
architecture module it names (`chipbench/arch.py`), and a stated norm
epsilon served through the residual stream's scale
(`weights.residual_scale`)."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import arch, control, flops, reference, run, weights
from chipbench.record import Run

HERE = Path(__file__).resolve().parent
TESTDATA = HERE / "testdata"
SEED = 2**31 + 17
LIMIT = 0.0015        # small-dense's, as in test_chipbench_control.py


def _config(name: str, **changes) -> dict:
    cj = json.loads(((HERE / "configs" if name.startswith(("granite",
                                                           "phi3"))
                      else TESTDATA) / f"{name}.json").read_text())
    cj.update(changes)
    return cj


def _served(cj: dict, seed: int = SEED):
    mix = json.loads((TESTDATA / "tiny-backlog.json").read_text())
    cell = {"name": cj["name"], "config": cj["name"],
            "traffic": "tiny-backlog", "chips": 1}
    return run.serve(cell, cj, mix, run.peaks_for("TPU v5 lite"),
                     seed=seed, seconds=2.0, trace=False,
                     log=lambda m: None)


def _correct(cj: dict, seed: int = SEED) -> bool:
    served = _served(cj, seed)
    checks, _ = run.check(served, cj, {"mean_logit_gap": {"limit": LIMIT}},
                          seed, log=lambda m: None)
    assert checks["sampled_requests"]["value"] >= 4
    return all(c["ok"] for c in checks.values())


@pytest.fixture
def test_archs(monkeypatch):
    monkeypatch.setattr(arch, "ARCHS_DIR", TESTDATA / "archs")


def test_granite_model_config_as_before():
    """What the fixed mapping of earlier code built for granite's file."""
    from repro.configs.base import ModelConfig, MoEConfig
    want = ModelConfig(
        name="granite-moe-1b-a400m", family="moe", num_layers=24,
        d_model=1024, num_heads=16, num_kv_heads=8, d_ff=512,
        vocab_size=49155, mlp_kind="swiglu", rope_theta=10000.0,
        tie_embeddings=True,
        moe=MoEConfig(num_experts=32, top_k=8, expert_ff=512,
                      capacity_factor=1.25, group_size=8))
    assert run.model_config(_config("granite-moe-1b-a400m")) == want


def test_phi3_model_config_is_the_published_stage():
    cfg = run.model_config(_config("phi3-medium-14b-pp4"))
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab_size) == (
        10, 5120, 40, 10, 128, 17920, 32064)
    assert cfg.moe is None and not cfg.tie_embeddings
    assert cfg.family == "dense" and cfg.mlp_kind == "swiglu"


def test_program_block_names_fields():
    cj = _config("tiny-moe")
    cj["program"] = dict(cj["program"], logit_softcap=30.0,
                         shared_expert_ff=16, moe_interleave=2,
                         ssm={"d_state": 8})
    cj["head_dim"] = 32
    cfg = run.model_config(cj)
    assert cfg.logit_softcap == 30.0 and cfg.head_dim == 32
    assert cfg.moe.shared_expert_ff == 16 and cfg.moe.interleave == 2
    assert cfg.moe.group_size == 2 and cfg.ssm.d_state == 8


@pytest.mark.parametrize("config,key", [
    ("tiny-dense", "no_such_field"),
    ("tiny-dense", "rms_norm_eps"),      # the program has no such field
    ("tiny-dense", "d_model"),           # hidden_size gives it
    ("tiny-dense", "moe_group_size"),    # no experts to group
    ("tiny-moe", "moe_top_k"),           # num_experts_per_tok gives it
])
def test_unknown_program_key_raises(config, key):
    cj = _config(config)
    cj["program"] = dict(cj["program"], **{key: 1})
    with pytest.raises(ValueError, match=key):
        run.model_config(cj)


@pytest.mark.parametrize("config", ["tiny-moe", "small-dense"])
def test_default_arch_is_weights_and_reference(config):
    """A file without "arch": bitwise the params and gaps of direct calls
    to `weights` and `reference`."""
    cj = _config(config)
    a = arch.resolve(cj)
    assert a is arch.DEFAULT
    got, want = a.program_params(cj, SEED), weights.program_params(cj, SEED)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cj["vocab_size"], n).tolist() for n in (30, 50)]
    args = (cj, SEED, seqs, [20, 41], 64)
    assert np.array_equal(a.served_gaps(*args), reference.served_gaps(*args))
    assert a.shape(cj) == flops.Shape.of(cj)


def test_arch_module_resolved_and_called(test_archs):
    """tiny-relu2 names a test-only module: the program serves its weights,
    its reference agrees, and the readers count its MLP."""
    cj = _config("tiny-relu2")
    mod = arch.resolve(cj)
    assert mod is not arch.DEFAULT and arch.resolve(dict(cj)) is mod
    params = mod.program_params(cj, SEED)
    assert set(params["blocks"][0]["mlp"]) == {"up", "down"}
    served = _served(cj)
    checks, _ = run.check(served, cj, {"mean_logit_gap": {"limit": LIMIT}},
                          SEED, log=lambda m: None)
    assert all(c["ok"] for c in checks.values())
    assert served.run.shape.layer_params() == \
        64 * 16 * (4 + 2 + 2 + 4) + 2 * 64 * 96
    assert isinstance(served.run.shape, mod.Shape)


def test_arch_module_is_what_makes_it_correct(test_archs):
    """The same program checked against the default reference (a SwiGLU
    MLP) is not correct."""
    cj = _config("tiny-relu2")
    served = _served(cj)
    plain = {k: v for k, v in cj.items() if k != "arch"}
    checks, _ = run.check(served, plain, {"mean_logit_gap":
                                          {"limit": LIMIT}},
                          SEED, log=lambda m: None)
    assert not checks["mean_logit_gap"]["ok"]


@pytest.mark.parametrize("bad", ["../weights", "no-such-arch"])
def test_arch_name_must_be_a_module_here(bad, test_archs):
    with pytest.raises(ValueError, match="arch"):
        arch.resolve({"arch": bad})


def test_stated_eps_reaches_the_norm():
    """A file stating rms_norm_eps 1e-2: served through `run.build`, the
    prefill and cached decode match the reference at 1e-2."""
    cj = _config("small-dense", rms_norm_eps=1e-2)
    assert weights.residual_scale(cj) == pytest.approx(0.01)
    assert _correct(cj)


def test_program_held_at_its_own_eps_fails(monkeypatch):
    """The same weights served with the stream unscaled, the program's
    norms at 1e-6, are not the model the file states."""
    monkeypatch.setattr(weights, "residual_scale", lambda cj: 1.0)
    assert not _correct(_config("small-dense", rms_norm_eps=1e-2))


def test_residual_scale_leaves_granite_as_it_was():
    cj = _config("granite-moe-1b-a400m")
    assert weights.residual_scale(cj) == 1.0
    m = weights.multipliers(cj)
    assert weights.folds(cj) == {
        "wq": m["attention"] * 8.0, "wo": 0.22, "down": 0.22, "embed": 12.0,
        "final_norm": 1 / 72}


def test_phi3_residual_scale():
    """Phi-3's 1e-5 at the program's 1e-6: the stream at sqrt(1/10); the
    untied unembed reads a normed stream, so the final norm keeps 1."""
    f = weights.folds(_config("phi3-medium-14b-pp4"))
    c = 0.1 ** 0.5
    assert f["wo"] == pytest.approx(c) and f["down"] == pytest.approx(c)
    assert f["embed"] == pytest.approx(c) and f["final_norm"] == 1.0
    assert f["wq"] == pytest.approx(1.0)


def test_stated_head_dim_reaches_weights_and_counts():
    from repro.models import init_params
    cj = _config("tiny-dense", head_dim=32)
    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                              run.model_config(cj)))
    got = weights.program_params(cj, SEED)
    assert jax.tree.map(lambda a: a.shape, got) == \
        jax.tree.map(lambda a: a.shape, want)
    assert flops.Shape.of(cj).hd == 32
    assert dict(reference.dims(cj))["hd"] == 32


def test_run_shape_goes_through_the_resolver(test_archs):
    r = Run(cell={"name": "c"}, config=_config("tiny-relu2"),
            mix={"kind": "backlog"}, peaks={}, seconds=1.0, setup_s=1.0,
            w0=0.0, w1=1.0, steps=[], arrival={}, tokens={}, times={})
    assert type(r.shape).__name__ == "Shape" and \
        type(r.shape) is not flops.Shape


@pytest.mark.parametrize("fault", [None] + sorted(control.FAULTS))
def test_control_and_faults_fail_with_a_stated_eps(fault):
    """Phi-3's layout and epsilon (small-dense at 1e-5, its stream scaled):
    the int8 control and every fault are not correct."""
    cj = _config("small-dense", rms_norm_eps=1e-5)
    mix = json.loads((TESTDATA / "tiny-backlog.json").read_text())
    cell = {"name": cj["name"], "config": cj["name"],
            "traffic": "tiny-backlog", "chips": 1}
    kw = ({"make_engine": control.build_int8} if fault is None
          else {"fault": control.FAULTS[fault]})
    served = run.serve(cell, cj, mix, run.peaks_for("TPU v5 lite"),
                       seed=SEED, seconds=2.0, trace=False,
                       log=lambda m: None, **kw)
    checks, _ = run.check(served, cj, {"mean_logit_gap": {"limit": LIMIT}},
                          SEED, log=lambda m: None)
    assert not all(c["ok"] for c in checks.values())
