"""Device time of the `attention` scope in the jitted prefill programs
(the pre-norm, q/k/v, rope, causal attention over the padded rows, the
K/V write and the output projection of every layer), per prefill call in
the traced window."""
from chipbench.inside import per_call_ms


def read(run):
    return per_call_ms(run, "prefill", "attention")
