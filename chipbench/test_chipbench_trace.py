"""The trace-to-metrics reduction, on a hand-made trace with known answers
and on a recorded TPU v5e trace of the granite decode and prefill steps
(`testdata/granite_trace.json.gz`: about 1.65 s of a serving window)."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as T

TESTDATA = Path(__file__).resolve().parent / "testdata"
DEV, HOST = "/device:TPU:0", T.HOST_PLANE
US = 1000                                          # ns


def _ev(plane, line, name, start_us, end_us):
    return T.Event(plane, line, name, start_us * US, (end_us - start_us) * US)


def hand_made():
    ops, mods = T.OPS_LINE, T.MODULES_LINE
    return [
        _ev(HOST, "main", T.WINDOW_SPAN, 0, 1000),
        _ev(HOST, "main", "executor.decode", 50, 420),
        _ev(HOST, "main", "chipbench.sleep", 650, 900),
        _ev(DEV, mods, "jit_decode_fn(11)", 90, 410),
        _ev(DEV, mods, "jit_prefill_fn(22)", 480, 1100),
        _ev(DEV, ops, "%while.4 = (s32[]) while(...)", 100, 400),
        _ev(DEV, ops, "%fusion.1 = bf16[8] fusion(...)", 150, 250),
        _ev(DEV, ops, "%paged_attention_kernel.9 = bf16[8] custom-call()",
            260, 300),
        _ev(DEV, ops, "%fusion.1 = bf16[8] fusion(...)", 500, 600),
        _ev(DEV, ops, "%dot.3 = f32[8] dot(...)", 950, 1100),
    ]


def test_busy_is_the_union_of_ops_clipped_to_the_window():
    r = T.reduce(hand_made())
    assert r.window_s == pytest.approx(1000e-6)
    assert r.busy_s == pytest.approx((300 + 100 + 50) * 1e-6)


def test_ops_count_their_own_time_by_program():
    op = T.reduce(hand_made()).op_s
    assert op["jit_decode_fn:%while.4"] == pytest.approx(160e-6)
    assert op["jit_decode_fn:%fusion.1"] == pytest.approx(100e-6)
    assert op["jit_prefill_fn:%fusion.1"] == pytest.approx(100e-6)
    assert op["jit_decode_fn:%paged_attention_kernel.9"] == pytest.approx(
        40e-6)
    assert op["jit_prefill_fn:%dot.3"] == pytest.approx(50e-6)   # clipped


def test_programs_are_timed_and_counted():
    r = T.reduce(hand_made())
    assert r.modules_matching("decode_fn") == (pytest.approx(320e-6), 1)
    assert r.modules_matching("prefill_fn") == (pytest.approx(520e-6), 1)
    assert r.ops_matching("paged_attention") == pytest.approx(40e-6)


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    gaps = dict(T.reduce(hand_made()).breakdown()["idle_gaps"])
    assert gaps["chipbench.sleep"] == pytest.approx(350e-6)
    assert gaps["executor.decode"] == pytest.approx(100e-6)
    assert gaps["engine"] == pytest.approx(100e-6)
    assert sum(gaps.values()) == pytest.approx(550e-6)


def test_a_trace_needs_one_window_and_a_device():
    ev = hand_made()
    with pytest.raises(RuntimeError):
        T.reduce(ev[1:])
    with pytest.raises(RuntimeError):
        T.reduce([e for e in ev if e.plane != DEV])


@pytest.fixture(scope="module")
def recorded():
    return T.reduce(T.load_events(TESTDATA / "granite_trace.json.gz"))


def test_recorded_trace_busy_and_idle_add_up(recorded):
    r = recorded
    assert 0 < r.busy_s <= r.window_s
    assert sum(r.op_s.values()) == pytest.approx(r.busy_s, rel=1e-9)
    idle = sum(s for _, s in r.gaps)
    assert r.busy_s + idle == pytest.approx(r.window_s, rel=1e-9)


def test_recorded_trace_programs_and_kernel(recorded):
    r = recorded
    dec_s, dec_n = r.modules_matching("decode_fn")
    pre_s, pre_n = r.modules_matching("prefill_fn")
    assert (dec_n, pre_n) == (3, 1)
    kernel = r.ops_matching("paged_attention")
    assert 0 < kernel < dec_s
    assert pre_s > dec_s / dec_n


@pytest.mark.parametrize("key", ["device_ops", "idle_gaps"])
def test_breakdown_lists_at_most_ten(recorded, key):
    b = recorded.breakdown()
    assert 0 < len(b[key]) <= 10
    assert all(isinstance(n, str) and s >= 0 for n, s in b[key])
    secs = [s for _, s in b[key]]
    assert secs == sorted(secs, reverse=True)


def test_events_round_trip(tmp_path):
    ev = hand_made()
    T.save_events(ev, tmp_path / "e.json.gz")
    assert T.load_events(tmp_path / "e.json.gz") == ev


@pytest.mark.parametrize("name,short", [
    ("%fusion.134 = bf16[16,17920] fusion(bf16[16,1,5120] %a)", "%fusion.134"),
    ("%copy.3", "%copy.3"),
])
def test_op_names_keep_the_instruction(name, short):
    assert T.op_name(name) == short


def test_union_merges_overlaps():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
