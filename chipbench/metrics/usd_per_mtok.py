"""The paper's C_eff on this traffic: the chip's price per hour
(`peaks.json`) times the window, over the output tokens the window's
steps emitted. Each step emits one token for each row it served, at its
end; a step that runs across an edge of the window counts by the share
of its time inside it. (Counted whole, or not at all, a 64-row decode
step ending a few milliseconds either side of the close would move the
count by about 1% on the host's jitter alone.)"""


def read(run):
    tokens = 0.0
    for s in run.steps:
        inside = min(s.end, run.w1) - max(s.start, run.w0)
        if inside > 0:
            tokens += len(s.rids) * inside / (s.end - s.start)
    if not tokens:
        return None
    hours = (run.w1 - run.w0) / 3600
    return run.peaks["usd_per_chip_hour"] * hours / (tokens / 1e6)
