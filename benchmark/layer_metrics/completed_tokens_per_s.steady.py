"""``ServingMetrics.tokens_out`` over the window's clock, in tokens/s: what the
put-off ``big_serve_batch`` cell's ``serve_tokens_per_s`` counts, read here at
the steady cell's fixed rate (so it follows the offered load, not capacity)."""


def read(run):
    tokens = run.counters.get("tokens_out")
    return tokens / run.window_s if tokens and run.window_s else None
