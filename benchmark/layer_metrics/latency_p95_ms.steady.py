"""Nearest-rank 95th percentile of the latency from the due instant over
every request due in the window, in ms. A per-layer metric, not an
end-to-end one: between processes of the same code it spreads by 12-17 %
(PERF.md, section 2), more than any bound could hold."""


def read(run):
    return run.e2e.get("latency_p95_ms")
