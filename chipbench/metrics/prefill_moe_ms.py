"""Device time of the `moe` scope in the jitted prefill programs (the
pre-norm, routing, dispatch, experts and combine of every layer), per
prefill call in the traced window."""
from chipbench.inside import per_call_ms


def read(run):
    return per_call_ms(run, "prefill", "moe")
