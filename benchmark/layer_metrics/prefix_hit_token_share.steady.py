"""Prompt positions resumed from a prefix snapshot over prompt positions
admitted in the window (the runtime's ``resumed_tokens`` / ``prompt_tokens``)."""


def read(run):
    admitted = run.counters.get("prompt_tokens")
    if not admitted:
        return None
    return run.counters.get("resumed_tokens", 0) / admitted
