"""Share of the prefilled tokens that were prompt tokens, in %: the
window's increase of the program's `repro:prompt_tokens_total` over that
of `repro:prefill_tokens_computed_total` (every row of every prefill
matrix at its padded length), as the operator's meter scrapes them."""
PROMPT = "repro:prompt_tokens_total"
COMPUTED = "repro:prefill_tokens_computed_total"


def read(run):
    counters = getattr(run, "counters", None) or {}
    if not counters.get(COMPUTED):
        return None
    return 100 * counters.get(PROMPT, 0.0) / counters[COMPUTED]
