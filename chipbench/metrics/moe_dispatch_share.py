"""Share of the MoE layers' device time spent moving tokens to and from
the experts rather than in them, in %: the `moe/route` (router, top-k,
capacity positions, one-hots), `moe/dispatch` and `moe/combine` scopes
over the whole `moe` scope, in the decode and prefill programs of the
traced window."""
from chipbench.inside import scope_seconds

MOVING = ("route", "dispatch", "combine")


def read(run):
    moe = scope_seconds(run, r"decode_fn|prefill_fn", "moe")
    total = sum(moe.values())
    if not total:
        return None
    moving = sum(s for path, s in moe.items()
                 if path.partition("/")[2].split("/")[0] in MOVING)
    return 100 * moving / total
