"""The benchmark's harness end to end on the CPU, at test sizes: a cell
from a BENCHMARK.json of test configurations (`testdata/`), with the
platform check handed a described TPU and the served decode step taking
the Pallas paged kernel in interpret mode, as on the chip."""
import json
import math
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, run, traffic, weights
from chipbench.record import Run, percentile
from chipbench.trace_reduce import load_events, reduce
from chipbench.wallclock import Step, WallClock

HERE = Path(__file__).resolve().parent
TESTDATA = HERE / "testdata"
ROOT = HERE.parent
TPU = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture
def kernel_path(monkeypatch):
    """Serve decode through the Pallas kernel, interpreted on the CPU."""
    from repro.kernels.paged_attention import ops
    from repro.serving import runner

    def interpreted(*args, use_kernel=None):
        return ops.paged_attention(*args, use_kernel=True, interpret=True)
    monkeypatch.setattr(runner, "paged_attention", interpreted)


@pytest.fixture
def test_bench(tmp_path, monkeypatch):
    """A BENCHMARK.json whose cells serve the test configurations."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [
        {"name": n, "source": "test", "file": f"chipbench/testdata/{n}.json",
         "reduced": [], "why": "test"} for n in ("tiny-moe", "tiny-dense")]
    bench["workloads"] = [
        {"name": "moe.backlog", "config": "tiny-moe",
         "traffic": "tiny-backlog", "chips": 1, "why": "test"},
        {"name": "dense.poisson", "config": "tiny-dense",
         "traffic": "tiny-poisson", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    limits = tmp_path / "limits"
    limits.mkdir()
    for cell in ("moe.backlog", "dense.poisson"):
        (limits / f"{cell}.json").write_text(json.dumps(
            {"mean_logit_gap": {"limit": 1.0}}))
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", TESTDATA)
    monkeypatch.setattr(run, "LIMITS_DIR", limits)
    return path


@pytest.mark.parametrize("cell", ["moe.backlog", "dense.poisson"])
def test_result_line(cell, test_bench, kernel_path):
    lines = []
    out = run.measure(cell, 2**31 + 7, 1.5, False, bench_path=test_bench,
                      devices=[TPU], log=lines.append)
    assert list(out)[:5] == REQUIRED and list(out)[-1] == "checks"
    assert out["correct"] is True and out["attempted"] > 0
    assert out["device"]["kind"] == "TPU v5 lite"
    names = set(out["metrics"])
    assert {m["name"] for m in json.loads(test_bench.read_text())[
        "end_to_end"]} == names
    assert all(m["value"] > 0 and math.isfinite(m["value"])
               for m in out["metrics"].values())
    assert any(s == "compilations inside the window: 0" for s in lines)
    assert any(s.startswith("garbage collections inside the window: ")
               for s in lines)
    assert any(s.startswith("window steps: decode ") or
               s.startswith("window steps: prefill ") for s in lines)
    assert out["checks"]["mean_logit_gap"]["limit"] == 1.0
    json.dumps(out)


def test_gc_pauses_records_a_full_collection():
    import gc
    with run.GcPauses() as pauses:
        gc.collect()
    assert [g for _, _, g in pauses.pauses] == [2]
    assert pauses.describe().startswith("1, ")
    assert pauses not in gc.callbacks
    assert run.GcPauses().describe() == "none"


def test_open_loop_serves_every_request_due(test_bench, kernel_path):
    """An open loop counts the requests due in the window as attempted and
    waits past the close for their first tokens."""
    out = run.measure("dense.poisson", 5, 1.5, False, bench_path=test_bench,
                      devices=[TPU], log=lambda m: None)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["itl_p99_ms"]["value"] > 0


def test_no_tpu_raises(test_bench):
    with pytest.raises(run.NoChip):
        run.measure("moe.backlog", 1, 1.0, False, bench_path=test_bench,
                    log=lambda m: None)


def test_wall_clock_waits_for_arrivals_and_keeps_time():
    """Across an idle gap the engine's clock is the wall clock: the second
    request is prefilled when it is due, not when the first finishes."""
    cj = json.loads((TESTDATA / "tiny-dense.json").read_text())
    eng = run.build(cj, 3)
    run.warm_up(eng, (64,))
    from repro.serving.request import Request
    reqs = [Request(0, 0.0, 20, 4, prompt=list(range(20))),
            Request(1, 0.6, 20, 4, prompt=list(range(20)))]
    clock = WallClock(eng, spans=False)
    eng.ex = clock
    clock.start()
    eng.run(reqs)
    pre = [s for s in clock.steps if s.kind == "prefill"]
    longest = max(s.end - s.start for s in clock.steps)
    slack = 0.25                    # a sleep's overshoot on a busy host
    assert len(pre) == 2 and 0.6 <= pre[1].start < 0.6 + longest + slack
    assert clock.lateness and max(clock.lateness) < slack
    assert abs(eng.t - clock.now()) < longest + slack
    assert reqs[1].ttft == pytest.approx(clock.times[1][0] - 0.6)
    assert all(len(clock.tokens[r.rid]) == r.max_new_tokens for r in reqs)


def test_flops_hand_counts_granite_layer():
    cj = json.loads((HERE / "configs/granite-moe-1b-a400m.json").read_text())
    s = flops.Shape.of(cj)
    attn = 1024 * 64 * (16 + 8 + 8 + 16)             # q, k, v, o
    moe = 1024 * 32 + 8 * 3 * 1024 * 512             # router, 8 experts
    assert s.layer_params() == attn + moe == 15_761_408
    assert s.attn_flops(100) == 4 * 16 * 64 * 100
    assert s.decode_flops(100) == (24 * (2 * 15_761_408 + 409_600)
                                   + 2 * 1024 * 49155)
    assert s.prefill_flops(3) == (24 * (2 * 3 * 15_761_408 + 4 * 16 * 64 * 6)
                                  + 2 * 1024 * 49155)
    assert s.paged_attn_bytes(100) == 2 * 100 * 8 * 64 * 2 + 2 * 16 * 64 * 2


def test_flops_hand_counts_dense_layer():
    cj = json.loads((TESTDATA / "small-dense.json").read_text())
    s = flops.Shape.of(cj)
    assert s.layer_params() == 128 * 32 * (4 + 2 + 2 + 4) + 3 * 128 * 256 \
        == 147_456
    assert s.decode_flops(10) == (2 * (2 * 147_456 + 4 * 4 * 32 * 10)
                                  + 2 * 128 * 512)
    assert s.paged_attn_bytes(10) == 2 * 10 * 2 * 32 * 2 + 2 * 4 * 32 * 2


@pytest.mark.parametrize("group,drops", [(8, False), (256, True)])
def test_moe_group_of_top_k_drops_no_token(group, drops):
    """Every token routed to the same 8 of 32 experts: at group 8 (= top-k)
    the program matches a dropless mixture; at 256 it drops tokens."""
    from repro.configs.base import MoEConfig
    from repro.models import moe
    E, K, d, ff, T = 32, 8, 32, 16, 64
    cfg = MoEConfig(num_experts=E, top_k=K, expert_ff=ff, group_size=group)
    rng = np.random.default_rng(0)
    common = rng.normal(size=d)
    x = jnp.asarray(common + 0.1 * rng.normal(size=(1, T, d)), jnp.float32)
    router = 0.01 * rng.normal(size=(d, E))
    router[:, :K] += 0.5 * common[:, None] * (1 + np.arange(K))[None] / K
    p = {"router": jnp.asarray(router, jnp.float32),
         "experts_up": jnp.asarray(rng.normal(size=(E, d, ff)), jnp.float32),
         "experts_gate": jnp.asarray(rng.normal(size=(E, d, ff)),
                                     jnp.float32),
         "experts_down": jnp.asarray(rng.normal(size=(E, ff, d)),
                                     jnp.float32)}
    with jax.default_matmul_precision("highest"):
        got, _ = moe.apply_moe(p, x, cfg, "swiglu")
        h = x[0]
        probs = jax.nn.softmax(h @ p["router"], -1)
        top, idx = jax.lax.top_k(probs, K)
        assert set(np.unique(np.asarray(idx))) == set(range(K))   # skewed
        top = top / top.sum(-1, keepdims=True)
        ex = (jax.nn.silu(jnp.einsum("td,edf->tef", h, p["experts_gate"]))
              * jnp.einsum("td,edf->tef", h, p["experts_up"]))
        ex = jnp.einsum("tef,efd->ted", ex, p["experts_down"])
        want = jnp.einsum("tk,tkd->td", top,
                          jnp.take_along_axis(ex, idx[..., None], 1))
    close = np.allclose(np.asarray(got[0]), np.asarray(want), rtol=1e-4,
                        atol=1e-3)
    assert close is not drops


def test_traffic_same_work_every_seed():
    mix = traffic.load_mix("saturated")
    a = traffic.make_traffic(mix, vocab=49155, max_batch=32, seconds=40,
                             seed=2**31 + 12345)
    b = traffic.make_traffic(mix, vocab=49155, max_batch=32, seconds=40,
                             seed=3)
    sizes = lambda t: [(r.arrival_time, r.prompt_len,  # noqa: E731
                        r.max_new_tokens) for r in t.requests]
    assert sizes(a) == sizes(b)
    assert all(ra.prompt != rb.prompt
               for ra, rb in zip(a.requests, b.requests))
    assert all(r.prompt_len + r.max_new_tokens <= 1024 for r in a.requests)
    assert all(len(r.prompt) == r.prompt_len and max(r.prompt) < 49155
               for r in a.requests)
    # each block of max_batch requests spans the length distribution
    first = [r.prompt_len for r in a.requests[:32]]
    assert min(first) < 250 and max(first) > 700
    assert a.buckets == tuple(range(64, 961, 64))


def test_open_loop_arrivals_fill_the_span():
    mix = json.loads((TESTDATA / "tiny-poisson.json").read_text())
    t = traffic.make_traffic(mix, vocab=300, max_batch=4, seconds=3.0,
                             seed=11)
    arr = [r.arrival_time for r in t.requests]
    assert len(arr) == round(6.0 * 3.5) and arr == sorted(arr)
    assert arr[-1] == pytest.approx(3.5)


def test_usd_counts_the_tokens_emitted_in_the_window():
    """Output tokens the window's steps emitted, a step across the close
    by the share of its time inside, over the window's length."""
    steps = [Step("prefill", 0.0, 0.4, [1, 2], [9, 9], [9, 9]),
             Step("decode", 0.5, 0.9, [1, 2], [10, 10], [1, 1]),
             Step("decode", 0.9, 1.3, [1, 2], [11, 11], [1, 1]),
             Step("decode", 1.8, 2.2, [1, 2, 3], [12] * 3, [1] * 3),
             Step("decode", 2.2, 2.6, [1], [13], [1])]
    r = Run(cell={"name": "c"}, config={}, mix={"kind": "backlog"},
            peaks={"usd_per_chip_hour": 3.6}, seconds=1.5, setup_s=1.0,
            w0=0.5, w1=2.0, steps=steps, arrival={}, tokens={}, times={})
    assert run.reader("usd_per_mtok")(r) == pytest.approx(
        3.6 * (1.5 / 3600) / ((2 + 2 + 3 * 0.5) / 1e6))


def test_percentile_is_a_measured_value():
    assert percentile([3, 1, 2, 4], 50) == 2
    assert percentile(list(range(1, 101)), 95) == 95
    assert percentile([], 90) is None


def test_weights_match_the_program_layout():
    from repro.models import init_params
    for name in ("tiny-moe", "tiny-dense"):
        cj = json.loads((TESTDATA / f"{name}.json").read_text())
        want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                  run.model_config(cj)))
        got = weights.program_params(cj, 2**31 + 1)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
            jax.tree.map(lambda a: (a.shape, a.dtype), want)
        layer = weights.layer_weights(cj, 2**31 + 1, 1)
        assert bool(jnp.all(layer["wq"] == got["blocks"][0]["attn"]["wq"][1]))


def test_per_layer_readers_on_a_recorded_trace():
    """The per-layer readers on the recorded granite trace, with the steps
    the trace holds (three decode steps, one prefill)."""
    cj = json.loads((HERE / "configs/granite-moe-1b-a400m.json").read_text())
    red = reduce(load_events(TESTDATA / "granite_trace.json.gz"))
    steps = [Step("decode", 0.1, 0.2, list(range(32)), [600] * 32, [1] * 32)
             for _ in range(3)]
    steps.append(Step("prefill", 0.3, 0.9, [40], [500], [500]))
    peaks = run.peaks_for("TPU v5 lite")
    r = Run(cell={"name": "c"}, config=cj, mix={"kind": "backlog"},
            peaks=peaks, seconds=1.0, setup_s=1.0, w0=0.0, w1=2.0,
            steps=steps, arrival={}, tokens={}, times={},
            memory_peak_bytes=8 * 2**30, trace=red, traced=(0.0, 2.0))
    read = {m: run.reader(m)(r) for m in (
        "decode_step_ms", "prefill_step_ms", "device_idle_share",
        "paged_attn_roofline", "step_mfu", "peak_hbm_share",
        "decode_batch_mean")}
    dec_s, _ = red.modules_matching("decode_fn")
    assert read["decode_step_ms"] == pytest.approx(1e3 * dec_s / 3)
    assert read["prefill_step_ms"] == pytest.approx(
        1e3 * red.modules_matching("prefill_fn")[0])
    assert read["device_idle_share"] == pytest.approx(
        100 * (1 - red.busy_s / red.window_s))
    s = flops.Shape.of(cj)
    least = 3 * 32 * 24 * s.paged_attn_bytes(600) / peaks["hbm_bytes_per_s"]
    assert read["paged_attn_roofline"] == pytest.approx(
        100 * least / red.ops_matching("paged_attention"))
    assert 0 < read["paged_attn_roofline"] < 100
    assert 0 < read["step_mfu"] < 100
    assert read["peak_hbm_share"] == pytest.approx(50.0)
    assert read["decode_batch_mean"] == 32


@pytest.mark.parametrize("alone", [False, True])
def test_command_without_a_chip_prints_no_result(alone, tmp_path):
    """No TPU, or only BENCHMARK.json and chipbench/ in the directory: a
    non-zero exit and no result line."""
    cwd = ROOT
    if alone:
        cwd = tmp_path / "alone"
        cwd.mkdir()
        shutil.copy(ROOT / "BENCHMARK.json", cwd)
        shutil.copytree(HERE, cwd / "chipbench",
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "granite-moe.saturated", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
