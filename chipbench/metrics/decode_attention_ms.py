"""Device time of the `attention` scope in the jitted decode program (the
pre-norm, q/k/v, rope, the K/V write, the paged kernel and the output
projection of every layer), per decode call in the traced window."""
from chipbench.inside import per_call_ms


def read(run):
    return per_call_ms(run, "decode", "attention")
