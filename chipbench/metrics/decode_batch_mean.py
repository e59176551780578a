"""Mean number of requests a decode call served, over the decode calls
of the window (the engine's batch occupancy)."""


def read(run):
    steps = run.window_steps("decode")
    if not steps:
        return None
    return sum(len(s.rids) for s in steps) / len(steps)
