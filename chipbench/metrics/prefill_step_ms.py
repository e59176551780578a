"""Device time of the jitted prefill programs per prefill call in the
traced window."""
PROGRAM = r"prefill_fn"


def read(run):
    if run.trace is None:
        return None
    seconds, _ = run.trace.modules_matching(PROGRAM)
    calls = len(run.traced_steps("prefill"))
    if not calls or not seconds:
        return None
    return 1e3 * seconds / calls
