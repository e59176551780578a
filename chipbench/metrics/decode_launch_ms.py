"""Host time the executor takes to start a decode step, per decode call
in the traced window: its `executor.decode.inputs` (the host-to-device
copies) and `executor.decode.launch` (the jitted call until it returns)
spans, on the profiler trace's host plane."""
SPANS = ("executor.decode.inputs", "executor.decode.launch")


def read(run):
    spans = getattr(run.trace, "span_s", None) or {}
    if not all(s in spans for s in SPANS):
        return None
    secs = sum(spans[s][0] for s in SPANS)
    calls = spans[SPANS[1]][1]
    return 1e3 * secs / calls if calls else None
