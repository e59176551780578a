"""What the program marks from inside (`chipbench/inside.py`) and the
per-layer readers of it: named scopes, program host spans and counters,
on hand-made traces with known answers, on the CPU, and on a recorded
TPU v5e trace of the 64-slot granite cell with scopes and program spans
(`testdata/granite_scoped_trace.json.gz`: 2.364 s, three decode calls
and one prefill, its ops' op names resolved on the chip)."""
import json
from pathlib import Path

import pytest

from chipbench import inside as I
from chipbench import run
from chipbench import trace_reduce as T
from chipbench.record import Run
from chipbench.test_chipbench_run import TPU, test_bench  # noqa: F401
from chipbench.wallclock import Step

HERE = Path(__file__).resolve().parent
TESTDATA = HERE / "testdata"
DEV, HOST = "/device:TPU:0", T.HOST_PLANE
US = 1000                                          # ns
BODY = "jit(decode_fn)/while/body/closed_call/"


def _ev(plane, line, name, start_us, end_us, scope=""):
    return I.ScopedEvent(plane, line, name, start_us * US,
                         (end_us - start_us) * US, scope)


def hand_made():
    ops, mods = T.OPS_LINE, T.MODULES_LINE
    pre = "jit(prefill_fn)/while/body/closed_call/"
    return [
        _ev(HOST, "main", T.WINDOW_SPAN, 0, 1000),
        _ev(HOST, "main", "executor.decode", 40, 420),
        _ev(HOST, "main", "executor.decode.inputs", 40, 60),
        _ev(HOST, "main", "executor.decode.launch", 60, 95),
        _ev(HOST, "main", "executor.decode.wait", 95, 410),
        _ev(HOST, "main", "engine.update", 420, 470),
        _ev(HOST, "main", "executor.prefill", 480, 990),
        _ev(HOST, "main", "executor.prefill.wait", 520, 905),
        _ev(HOST, "main", "executor.decode.launch", 995, 1010),
        _ev(DEV, mods, "jit_decode_fn(11)", 100, 400),
        _ev(DEV, mods, "jit_prefill_fn(22)", 480, 1100),
        _ev(DEV, ops, "%while.4 = ...", 100, 380, "jit(decode_fn)/while"),
        _ev(DEV, ops, "%fusion.1 = ...", 110, 150, BODY + "attention/add"),
        _ev(DEV, ops, "%paged_attention.9 = ...", 150, 250,
            BODY + "attention/jit(paged_attention_kernel)/paged_attention/"
                   "pallas_call"),
        _ev(DEV, ops, "%scatter.2 = ...", 250, 270,
            BODY + "attention/kv_write/scatter"),
        _ev(DEV, ops, "%fusion.3 = ...", 270, 300,
            BODY + "moe/route/jit(cumsum)/apply_moe/reduce_window_sum"),
        _ev(DEV, ops, "%fusion.4 = ...", 300, 340,
            BODY + "moe/experts/egcd,edf->egcf/dot_general"),
        _ev(DEV, ops, "%copy.5 = ...", 340, 360,
            "jit(decode_fn)/while/body/dynamic_slice"),
        _ev(DEV, ops, "%fusion.6 = ...", 380, 400,
            "jit(decode_fn)/unembed/argmax"),
        _ev(DEV, ops, "%fusion.7 = ...", 500, 700, pre + "attention/dot"),
        _ev(DEV, ops, "%fusion.8 = ...", 700, 800,
            pre + "moe/dispatch/gsec,gsd->egcd/dot_general"),
        _ev(DEV, ops, "%fusion.9 = ...", 800, 900, pre + "moe/combine/mul"),
        _ev(DEV, ops, "%fusion.10 = ...", 950, 1100, pre + "mlp/dot"),
    ]


@pytest.mark.parametrize("op_name,path", [
    (BODY + "attention/kv_write/scatter", "attention/kv_write"),
    (BODY + "moe/route/jit(cumsum)/apply_moe/reduce_window_sum",
     "moe/route"),
    (BODY + "moe/experts/egcd,edf->egcf/dot_general", "moe/experts"),
    ("jit(decode_fn)/jit(main)/embed/jit(_take)/gather", "embed"),
    ("jit(decode_fn)/while/body/dynamic_slice", ""),
    ("jit(decode_fn)/while", ""),
    (BODY + "attention/kv_write/reshape;attention/reshape",
     "attention/kv_write"),
    ("", ""),
])
def test_scope_path_keeps_the_named_scopes(op_name, path):
    assert I.scope_path(op_name) == path


def test_ops_take_the_op_names_of_the_program_that_ran_them():
    """Two prefill programs (two prompt lengths) number their instructions
    alike; an execution takes its op names from the one whose HLO its ops'
    events begin."""
    short = I.op_names(
        '%fusion.1 = f32[2,64] fusion(f32[2,64] %p), kind=kLoop, '
        'metadata={op_name="jit(prefill_fn)/embed/mul"}\n'
        'ROOT %copy.2 = f32[2,64] copy(f32[2,64] %fusion.1)\n')
    long = I.op_names(
        '%fusion.1 = f32[2,128] fusion(f32[2,128] %p), kind=kLoop, '
        'metadata={op_name="jit(prefill_fn)/while/body/closed_call/moe/'
        'route/exp"}\n'
        'ROOT %copy.2 = f32[2,128] copy(f32[2,128] %fusion.1), '
        'metadata={op_name="jit(prefill_fn)/unembed/copy"}\n')
    assert short["%copy.2"] == ("%copy.2 = f32[2,64] copy(f32[2,64] "
                                "%fusion.1)", "")
    # a fusion left without metadata: the first op_name it calls
    fused = I.op_names(
        '%fused_computation.3 (param_0: f32[2,64]) -> f32[2,64] {\n'
        '  %param_0 = f32[2,64] parameter(0)\n'
        '  ROOT %t.1 = f32[2,64] transpose(%param_0), '
        'metadata={op_name="jit(prefill_fn)/attention/kv_write/transpose"}\n'
        '}\n\n'
        'ENTRY %main.9 (p: f32[2,64]) -> f32[2,64] {\n'
        '  ROOT %fusion.5 = f32[2,64] fusion(%p), kind=kCustom, '
        'calls=%fused_computation.3\n}\n')
    assert fused["%fusion.5"][1] == "jit(prefill_fn)/attention/kv_write/" \
        "transpose"
    ev = [_ev(DEV, T.MODULES_LINE, "jit_prefill_fn(7)", 0, 10),
          _ev(DEV, T.MODULES_LINE, "jit_prefill_fn(8)", 20, 30),
          _ev(DEV, T.OPS_LINE, "%fusion.1 = f32[2,128] fusion(f32[2,128] %p)"
              ", kind=kLoop", 1, 2),
          _ev(DEV, T.OPS_LINE, "%copy.2 = f32[2,128] copy(f32[2,128] "
              "%fusion.1)", 2, 3),
          _ev(DEV, T.OPS_LINE, "%fusion.1 = f32[2,64] fusion(f32[2,64] %p), "
              "kind=kLoop", 21, 22),
          _ev(HOST, "main", "executor.prefill.launch", 0, 1)]
    got = I.with_op_names(ev, {"jit_prefill_fn": [short, long]})
    assert [e.scope for e in got if e.line == T.OPS_LINE] == [
        "jit(prefill_fn)/while/body/closed_call/moe/route/exp",
        "jit(prefill_fn)/unembed/copy", "jit(prefill_fn)/embed/mul"]
    assert len(got) == len(ev)


def test_scopes_take_their_ops_own_time():
    """A loop's own time and the scan's slicing are unscoped; nested
    functions count to the scope they were called in; the window clips."""
    got = I.reduce(hand_made()).scope_s
    want = {"jit_decode_fn:unscoped": 30 + 20,
            "jit_decode_fn:attention": 40 + 100,
            "jit_decode_fn:attention/kv_write": 20,
            "jit_decode_fn:moe/route": 30, "jit_decode_fn:moe/experts": 40,
            "jit_decode_fn:unembed": 20, "jit_prefill_fn:attention": 200,
            "jit_prefill_fn:moe/dispatch": 100,
            "jit_prefill_fn:moe/combine": 100, "jit_prefill_fn:mlp": 50}
    assert got == pytest.approx({k: v * 1e-6 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(T.reduce(hand_made()).busy_s)


def test_idle_gaps_are_named_by_the_innermost_span():
    """Inside a call, after its inner spans closed, the gap belongs to the
    call itself, not to the scheduler."""
    gaps = dict(I.reduce(hand_made()).breakdown()["idle_gaps"])
    assert gaps == pytest.approx({"executor.decode.inputs": 100e-6,
                                  "engine.update": 100e-6,
                                  "executor.prefill": 50e-6})
    old = dict(T.reduce(hand_made()).breakdown()["idle_gaps"])
    assert old["engine"] == pytest.approx(50e-6)


def test_host_spans_count_those_inside_the_window():
    spans = I.reduce(hand_made()).span_s
    assert spans["executor.decode.launch"] == (pytest.approx(35e-6), 1)
    assert spans["executor.decode.wait"] == (pytest.approx(315e-6), 1)
    assert spans["engine.update"] == (pytest.approx(50e-6), 1)
    assert T.WINDOW_SPAN not in spans


def _run(red, steps, counters=None, traced=(0.0, 2.0)):
    cj = json.loads((HERE / "configs/granite-moe-1b-a400m.json").read_text())
    r = Run(cell={"name": "c"}, config=cj, mix={"kind": "backlog"},
            peaks=run.peaks_for("TPU v5 lite"), seconds=1.0, setup_s=1.0,
            w0=0.0, w1=2.0, steps=steps, arrival={}, tokens={}, times={},
            trace=red, traced=traced)
    if counters is not None:
        r.counters = counters
    return r


def _with_inside(events, steps, counters):
    red = T.reduce(events)
    ins = I.reduce(events)
    red.scope_s, red.span_s = ins.scope_s, ins.span_s
    return _run(red, steps, counters)


STEPS = [Step("decode", 0.1, 0.2, [0, 1], [600, 600], [1, 1]),
         Step("prefill", 0.3, 0.9, [2], [500], [500])]


def test_readers_on_a_hand_made_run():
    r = _with_inside(hand_made(), STEPS, {
        "repro:prompt_tokens_total": 490.0,
        "repro:prefill_tokens_computed_total": 64 * 512.0})
    read = {m: run.reader(m)(r) for m in I.READERS}
    assert read == pytest.approx({
        "decode_attention_ms": 1e-3 * 160, "decode_moe_ms": 1e-3 * 70,
        "prefill_attention_ms": 1e-3 * 200, "prefill_moe_ms": 1e-3 * 200,
        "moe_dispatch_share": 100 * 230 / 270,
        "prefill_useful_share": 100 * 490 / (64 * 512),
        "decode_launch_ms": 1e-3 * 55})


@pytest.mark.parametrize("metric", I.READERS)
def test_readers_find_nothing_where_the_run_has_nothing(metric):
    """A run of `run.py` (no scopes, program spans or counters), and a run
    without a trace: None."""
    plain = _run(T.reduce(T.load_events(TESTDATA / "granite_trace.json.gz")),
                 STEPS)
    assert run.reader(metric)(plain) is None
    assert run.reader(metric)(_run(None, STEPS)) is None


def test_recordings_without_scopes_still_load(tmp_path):
    ev = T.load_events(TESTDATA / "granite_trace.json.gz")
    T.save_events(ev, tmp_path / "e.json.gz")
    got = I.load_events(tmp_path / "e.json.gz")
    assert [e.scope for e in got] == [""] * len(ev)
    scopes = I.reduce(got).scope_s
    assert all(k.endswith(":unscoped") for k in scopes)
    assert sum(scopes.values()) == pytest.approx(T.reduce(ev).busy_s)


def test_window_counters_match_the_prefills_served(test_bench):  # noqa: F811
    """The program's prompt tokens over the window, as the meter scrapes
    them, are the prompt tokens of the prefills recorded between the same
    two scrapes; the computed ones cover every row at its padded width."""
    served, ins, _, scrapes = I.serve_inside(
        "moe.backlog", 2**31 + 3, 1.5, lambda m: None, trace=False,
        bench_path=test_bench, devices=[TPU])
    assert ins is None and len(scrapes) == 2
    r = served.run
    (_, n0), (_, n1) = scrapes
    pre = [s for s in r.steps[n0:n1] if s.kind == "prefill"]
    assert pre and r.counters["repro:prompt_tokens_total"] == sum(
        sum(s.new_tokens) for s in pre)
    computed = r.counters["repro:prefill_tokens_computed_total"]
    rows = r.config["engine"]["max_batch"]
    assert computed % (64 * rows) == 0
    assert computed >= 64 * rows * len(pre)
    assert 0 < run.reader("prefill_useful_share")(r) < 100


@pytest.fixture(scope="module")
def recorded():
    """The recorded scoped trace, with the steps it holds: three 64-row
    decode calls and one prefill."""
    ev = I.load_events(TESTDATA / "granite_scoped_trace.json.gz")
    steps = [Step("decode", 0.1, 0.2, list(range(64)), [600] * 64, [1] * 64)
             for _ in range(3)]
    steps.append(Step("prefill", 0.3, 0.9, [70], [500], [500]))
    return _with_inside(ev, steps, None)


def test_readers_on_a_recorded_scoped_trace(recorded):
    read = {m: run.reader(m)(recorded) for m in I.READERS}
    assert read == pytest.approx({
        "decode_attention_ms": 205.05884766666694,
        "decode_moe_ms": 3.6609543333333328,
        "prefill_attention_ms": 357.7867669999994,
        "prefill_moe_ms": 1137.678924,
        "moe_dispatch_share": 22.028398512398674,
        "prefill_useful_share": None,
        "decode_launch_ms": 1.5479000000000003}, rel=1e-9)
    # attention holds the paged kernel; the scopes fit in their programs
    kernel_ms = 1e3 * recorded.trace.ops_matching("paged_attention") / 3
    assert kernel_ms < read["decode_attention_ms"]
    for kind in ("decode", "prefill"):
        scoped = sum(read[f"{kind}_{top}_ms"] for top in ("attention", "moe"))
        assert scoped < run.reader(f"{kind}_step_ms")(recorded)
