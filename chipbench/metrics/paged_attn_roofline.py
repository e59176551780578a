"""The paged-attention kernel's share of its roofline, in %: the least
time the decode attention of the live contexts needs (the larger of its
bytes over HBM bandwidth and its operations over bf16 peak; K and V of
each row's own keys, the query and the output, every layer) over the
device time of the kernel's events in the traced window."""
KERNEL = r"paged_attention|_pa_kernel"


def bound(run):
    """(least seconds, which resource bounds them) for the traced window."""
    shape = run.shape
    nbytes = flops = 0
    for s in run.traced_steps("decode"):
        for k in s.ctx:
            nbytes += shape.decode_attn_bytes(k)
            flops += shape.decode_attn_flops(k)
    t_mem = nbytes / run.peaks["hbm_bytes_per_s"]
    t_cmp = flops / run.peaks["bf16_flops_per_s"]
    return max(t_mem, t_cmp), ("memory" if t_mem >= t_cmp else "compute")


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.ops_matching(KERNEL)
    least, _ = bound(run)
    if not kernel_s or not least:
        return None
    return 100 * least / kernel_s
