"""Event-driven fast-forward scheduler equivalence (ISSUE 1).

The fast path must reproduce the reference per-token loop exactly:
identical scheduling decisions (admissions, completions, failure
re-queues) and timings within float-rounding tolerance — across Poisson
and bursty gamma arrivals, failure injection, horizon truncation and
re-entrant runs. Plus the closed-form `decode_time_multi` against the
per-step sum, and the satellite regressions (fail_running before run,
MetricsRegistry.reset)."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.serving import (ArrivalSpec, Engine, EngineConfig, SimExecutor,
                           synth_requests)
from repro.serving.request import Request, RequestState
from repro.simulate import StepTimeModel, V5E, V5P

RTOL = 1e-9


def _engine(fast_forward, arch="llama31-8b", hw=V5E, max_batch=32,
            num_pages=8192, max_pages_per_seq=64, **ecfg_kw):
    cfg = get_config(arch)
    stm = StepTimeModel(cfg, hw)
    return Engine(EngineConfig(max_batch=max_batch, page_size=16,
                               num_pages=num_pages,
                               max_pages_per_seq=max_pages_per_seq,
                               fast_forward=fast_forward, **ecfg_kw),
                  SimExecutor(cfg, stm))


def _run_pair(spec, *, horizon=None, failure_times=(), **ekw):
    out = []
    for ff in (False, True):
        eng = _engine(ff, **ekw)
        reqs = synth_requests(spec)
        eng.run(reqs, horizon=horizon, failure_times=failure_times)
        out.append((eng, reqs))
    return out


def _assert_equivalent(ref, fast):
    (eref, rref), (efast, rfast) = ref, fast
    assert abs(eref.t - efast.t) <= RTOL * max(1.0, eref.t)
    assert np.isclose(eref.mean_inflight(), efast.mean_inflight(),
                      rtol=RTOL, atol=1e-12)
    for a, b in zip(rref, rfast):
        assert a.state == b.state
        assert a.tokens_out == b.tokens_out
        assert a.retries == b.retries
        assert (a.finish_time is None) == (b.finish_time is None)
        for ta, tb in ((a.finish_time, b.finish_time),
                       (a.first_token_time, b.first_token_time)):
            assert (ta is None) == (tb is None)
            if ta is not None:
                assert abs(ta - tb) <= RTOL * max(1.0, abs(ta))
    for key in ("repro:generation_tokens_total",
                "repro:prompt_tokens_total",
                "repro:request_success_total",
                "repro:request_preempted_total",
                "repro:decode_kv_pages_live_total",
                "repro:decode_kv_pages_table_total"):
        assert eref.metrics.get(key) == efast.metrics.get(key), key


CASES = [
    pytest.param(dict(lam=2, n_requests=60, seed=0), {}, {}, id="idle"),
    pytest.param(dict(lam=20, n_requests=120, seed=1), {}, {}, id="loaded"),
    pytest.param(dict(lam=80, n_requests=200, seed=2), {}, {},
                 id="saturated"),
    pytest.param(dict(lam=20, n_requests=100, seed=3, process="gamma",
                      cv=2.0), {}, {}, id="bursty-gamma"),
    pytest.param(dict(lam=15, n_requests=80, seed=4, io_shape="variable"),
                 {}, dict(max_pages_per_seq=512, num_pages=16384),
                 id="variable-shape"),
    pytest.param(dict(lam=20, n_requests=40, seed=2),
                 dict(failure_times=[0.5, 1.5]), {}, id="failures"),
    pytest.param(dict(lam=20, n_requests=150, seed=5), dict(horizon=4.0),
                 {}, id="horizon-truncated"),
    pytest.param(dict(lam=10, n_requests=50, seed=6),
                 dict(failure_times=[0.3], horizon=12.0), {},
                 id="failures+horizon"),
]


@pytest.mark.parametrize("case,runkw,ekw", CASES)
def test_fast_forward_matches_reference(case, runkw, ekw):
    spec = ArrivalSpec(**case)
    ref, fast = _run_pair(spec, **runkw, **ekw)
    _assert_equivalent(ref, fast)


# ---- idle-regime edges (ISSUE 3) -------------------------------------


def _mk_reqs(arrivals, prompt_len=64, max_new=24):
    from repro.serving.request import Request
    return [Request(rid=i, arrival_time=float(t), prompt_len=prompt_len,
                    max_new_tokens=max_new)
            for i, t in enumerate(arrivals)]


def _run_pair_reqs(arrivals, **ekw):
    out = []
    for ff in (False, True):
        eng = _engine(ff, **ekw)
        reqs = _mk_reqs(arrivals)
        eng.run(reqs)
        out.append((eng, reqs))
    return out


def test_idle_co_arrivals_admitted_in_one_wakeup():
    """Batch and queue both empty, several requests arriving at the same
    instant: the idle jump must land once and admit the whole co-arrival
    group in that wakeup — and still match the reference exactly."""
    ref, fast = _run_pair_reqs([1.0, 1.0, 1.0, 9.0, 9.0])
    _assert_equivalent(ref, fast)
    efast, rfast = fast
    # all co-arrivals share one admission instant (same prefill batch)
    assert len({r.first_token_time for r in rfast[:3]}) == 1
    assert len({r.first_token_time for r in rfast[3:]}) == 1
    # two idle gaps + per-group events only: far below one iteration per
    # token, and below even one iteration per request-arrival pair
    assert efast.n_iterations < ref[0].n_iterations / 4
    assert efast.n_ff_jumps >= 2


def test_arrival_exactly_at_completion_event():
    """An arrival whose timestamp exactly equals a completion event must
    take the same scheduler path on both engines (the fast path treats
    arrivals as non-events while a batch runs; the tie must not let the
    jump overshoot the admission)."""
    probe = _engine(True)
    lone = _mk_reqs([0.0])
    probe.run(lone)
    t_done = lone[0].finish_time
    assert t_done is not None and t_done > 0
    ref, fast = _run_pair_reqs([0.0, t_done])
    _assert_equivalent(ref, fast)
    # the second request was admitted at (not after) the completion time
    assert fast[1][1].first_token_time >= t_done


def test_arrival_during_final_decode_burst():
    """Arrival strictly inside the last decode burst of an otherwise
    idle engine: the burst must stop at the arrival so admission happens
    at the same clock on both paths."""
    probe = _engine(True)
    lone = _mk_reqs([0.0])
    probe.run(lone)
    mid = lone[0].finish_time * 0.61803
    ref, fast = _run_pair_reqs([0.0, mid])
    _assert_equivalent(ref, fast)


@pytest.mark.parametrize("lam", [0.5, 2.0, 5.0])
def test_idle_regime_equivalence_and_speedup(lam):
    """lambda <= 5 (the idle regime the PR 2 follow-up targeted): the
    fast path must stay exactly equivalent to the per-token reference
    while doing a fraction of the scheduler iterations."""
    spec = ArrivalSpec(lam=lam, n_requests=60, seed=11)
    ref, fast = _run_pair(spec)
    _assert_equivalent(ref, fast)
    assert fast[0].n_ff_jumps > 0
    assert fast[0].n_iterations < ref[0].n_iterations / 2


def test_fast_forward_reentrant_horizon_loop():
    """Meter-tick style: repeated run() calls under a growing horizon must
    resume identically on both paths."""
    res = {}
    for ff in (False, True):
        eng = _engine(ff)
        reqs = synth_requests(ArrivalSpec(lam=10, n_requests=100, seed=0))
        h = 0.0
        while any(r.finish_time is None for r in reqs):
            h += 2.0
            eng.run(reqs, horizon=h)
            assert h < 3600
        res[ff] = (eng, reqs)
    _assert_equivalent(res[False], res[True])


def test_fast_forward_littles_law():
    """The jump path must preserve the time-weighted in-flight integral:
    mean_inflight ~= lambda_effective * mean residence."""
    eng = _engine(True, max_batch=128, num_pages=16384)
    reqs = synth_requests(ArrivalSpec(lam=5, n_requests=300, seed=0))
    eng.run(reqs)
    done = [r for r in reqs if r.finish_time is not None]
    lam_eff = len(done) / eng.t
    W = float(np.mean([r.e2e for r in done]))
    N = eng.mean_inflight()
    assert abs(N - lam_eff * W) / max(N, 1e-9) < 0.15, (N, lam_eff * W)


def test_fast_forward_actually_jumps():
    """Sanity: the fast path takes far fewer scheduler iterations than the
    per-token reference on the same workload."""
    (eref, _), (efast, _) = _run_pair(ArrivalSpec(lam=20, n_requests=120,
                                                  seed=1))
    assert efast.n_ff_jumps > 0
    assert efast.n_iterations < eref.n_iterations / 4
    assert efast.n_decode_steps == eref.n_decode_steps


def test_decode_time_multi_matches_stepwise_sum():
    """Closed-form k-step decode sum vs the naive per-step loop."""
    for arch, hw in (("llama31-8b", V5E), ("qwen3-30b-a3b", V5P),
                     ("mixtral-8x7b", V5E)):
        stm = StepTimeModel(get_config(arch), hw)
        for batch in (1, 8, 64, 256):
            for ctx0 in (0.0, 37.5, 512.0, 4096.0):
                for k in (1, 2, 7, 100, 1000):
                    want = sum(stm.decode_time(batch, ctx0 + i)
                               for i in range(k))
                    got = stm.decode_time_multi(batch, ctx0, k)
                    assert got == pytest.approx(want, rel=1e-9), \
                        (arch, batch, ctx0, k)
    assert stm.decode_time_multi(8, 100.0, 0) == 0.0
    assert stm.decode_time_multi(0, 0.0, 5) == \
        pytest.approx(5 * stm.decode_time(0, 0.0))


def test_real_executor_fallback_keeps_fast_path_correct():
    """An executor without closed-form jumps (decode_multi loops per step)
    still completes everything under the fast scheduler."""

    class SteppingSim(SimExecutor):
        """Sim timing, but per-step decode_multi like RealExecutor."""
        needs_tokens = True

        def decode_multi(self, tokens, active, block_tables, context_lens,
                         max_steps, time_budget=None):
            cur = np.array(tokens)
            total, steps = 0.0, 0
            ctx = np.array(context_lens)
            while steps < int(max_steps):
                nxt, dt = self.decode(cur, active, block_tables,
                                      context_lens=ctx)
                cur[active] = nxt[active]
                ctx[active] += 1
                total += dt
                steps += 1
                if time_budget is not None and total >= time_budget:
                    break
            return cur, total, max(steps, 1)

    cfg = get_config("llama31-8b")
    stm = StepTimeModel(cfg, V5E)
    results = {}
    for ex in (SimExecutor(cfg, stm), SteppingSim(cfg, stm)):
        eng = Engine(EngineConfig(max_batch=32, page_size=16,
                                  num_pages=8192, max_pages_per_seq=64,
                                  fast_forward=True), ex)
        reqs = synth_requests(ArrivalSpec(lam=20, n_requests=60, seed=7))
        eng.run(reqs)
        results[type(ex).__name__] = (eng, reqs)
    _assert_equivalent(results["SimExecutor"], results["SteppingSim"])


def test_fail_running_before_run_does_not_raise():
    """Satellite: `_requeue` is initialised in __init__, so a driver can
    inject a failure before ever calling run()."""
    eng = _engine(True)
    reqs = synth_requests(ArrivalSpec(lam=5, n_requests=3, seed=0))
    r = reqs[0]
    slot = eng.pm.admit(r.prompt_len, r.max_new_tokens)
    r.slot = slot
    eng.slot_req[slot] = r
    eng.fail_running(1.0)                       # must not raise
    assert eng._requeue and eng._requeue[0] is r
    assert r.state == RequestState.QUEUED
    # the re-queued request is picked up by a subsequent run()
    eng.run(reqs)
    assert all(q.finish_time is not None for q in reqs)


def test_metrics_reset_clears_gauges_and_keeps_bound_hists():
    """Satellite: reset() flushes counters, gauges AND histogram contents
    (in place, so the engine's pre-bound histogram refs stay live)."""
    eng = _engine(True)
    reqs = synth_requests(ArrivalSpec(lam=10, n_requests=20, seed=1))
    eng.run(reqs)
    m = eng.metrics
    assert m.get("repro:time_seconds") > 0
    assert m.hists["repro:e2e_request_latency_seconds"].n == 20
    m.reset()
    assert m.counters == {} and m.gauges == {}
    assert m.hists["repro:e2e_request_latency_seconds"].n == 0
    # a fresh measured run records into the same (cleared) histograms
    eng.reset_measurement()
    reqs2 = synth_requests(ArrivalSpec(lam=10, n_requests=15, seed=2))
    eng.run(reqs2)
    assert m.hists["repro:e2e_request_latency_seconds"].n == 15
    assert sum(m.hists["repro:e2e_request_latency_seconds"].counts) == 15


@pytest.mark.parametrize("fast_forward", [True, False])
def test_decode_kv_page_counters_match_a_hand_count(fast_forward):
    """Per decode step, the pages of K/V the running rows attend over
    (context + 1 tokens, 16 a page) and the table entries a walk of every
    page holds (rows x max_pages_per_seq), on both scheduler paths."""
    eng = _engine(fast_forward, max_batch=4, max_pages_per_seq=8)
    # (prompt, max_new): one prefill, then 3, 2 and 1 decode steps
    reqs = [Request(i, 0.0, n, m)
            for i, (n, m) in enumerate([(15, 4), (16, 3), (40, 2)])]
    eng.run(reqs)
    assert eng.n_decode_steps == 3
    # attends over 16, 17, 18 | 17, 18 | 41 tokens
    live = (1 + 2 + 2) + (2 + 2) + 3
    table = (3 + 2 + 1) * 8
    assert eng.metrics.get("repro:decode_kv_pages_live_total") == live
    assert eng.metrics.get("repro:decode_kv_pages_table_total") == table
