"""Process start to the window's opening: engine and weights, traffic,
one call of every prefill length and the decode step (compiled, or
loaded from the persistent cache), and serving until the window opens."""


def read(run):
    return run.setup_s
