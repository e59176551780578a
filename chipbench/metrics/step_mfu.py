"""Operations the served tokens need over what the chip could do in the
traced window, in %: the admitted prompts' prefill and every decoded
token (`chipbench/flops.py`: the top-k experts a token uses, attention
over its own context, no padding) over window x bf16 peak."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    shape = run.shape
    flops = sum(shape.prefill_flops(n) for s in run.traced_steps("prefill")
                for n in s.new_tokens)
    flops += sum(shape.decode_flops(k) for s in run.traced_steps("decode")
                 for k in s.ctx)
    if not flops:
        return None
    peak = run.peaks["bf16_flops_per_s"] * run.trace.window_s
    return 100 * flops / peak
