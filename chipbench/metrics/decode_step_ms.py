"""Device time of the jitted decode program per decode call in the
traced window."""
PROGRAM = r"decode_fn"


def read(run):
    if run.trace is None:
        return None
    seconds, _ = run.trace.modules_matching(PROGRAM)
    calls = len(run.traced_steps("decode"))
    if not calls or not seconds:
        return None
    return 1e3 * seconds / calls
