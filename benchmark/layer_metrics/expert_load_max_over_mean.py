"""The fullest held expert's assignments over the mean held expert's, over
the window's steps (``moe_tokens_held_max / moe_tokens_held_mean`` of the
loss's step metrics): 1 is a perfectly even router."""


def read(run):
    mean = run.counters.get("moe_tokens_held_mean")
    top = run.counters.get("moe_tokens_held_max")
    if not mean or top is None:
        return None
    return top / mean
