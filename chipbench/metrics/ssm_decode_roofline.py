"""The Mamba-2 decode state update's share of its roofline, in %: the
least time the traced decode steps' state updates need (for each active
row and Mamba layer, the float32 state read and written, the conv tail
and the x, B, C, dt and y vectors, at HBM bandwidth; or the update's
operations at bf16 peak, whichever is larger; the arch module's
`shape`) over the device time of the `ssm_state_update` kernel's events.
None for a configuration whose shape counts no such update."""
KERNEL = r"ssm_state_update"


def bound(run):
    """(least seconds, which resource bounds them) for the traced window."""
    shape = run.shape
    rows = sum(len(s.rids) for s in run.traced_steps("decode"))
    t_mem = rows * shape.ssm_decode_bytes() / run.peaks["hbm_bytes_per_s"]
    t_cmp = rows * shape.ssm_decode_flops() / run.peaks["bf16_flops_per_s"]
    return max(t_mem, t_cmp), ("memory" if t_mem >= t_cmp else "compute")


def read(run):
    if run.trace is None or not hasattr(run.shape, "ssm_decode_bytes"):
        return None
    kernel_s = run.trace.ops_matching(KERNEL)
    least, _ = bound(run)
    if not kernel_s or not least:
        return None
    return 100 * least / kernel_s
