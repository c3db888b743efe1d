"""One general traffic generator, driven by a mix's data file.

A mix fixes the *work*: the multiset of prompt lengths, the number of
callers or the arrival rate, the number of new tokens. The seed only
permutes that multiset and fills the token ids, so every seed gives the
same set of sizes and arrivals in another order (what keeps two seeds'
runs comparable). Nothing here touches JAX.

Lengths: ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
"max": b, "count": n}`` takes the n quantiles (i + 0.5) / n of the
log-normal, rounds and clips them: a heavy-tailed sentence-length
distribution with nothing random in it.

Arrivals (open loop): ``{"process": "poisson", "rate_per_s": r}`` takes the
n = round(r * seconds) quantiles of the exponential gap, scaled to sum to
n / r, permuted by the seed, and accumulates them into due times.

No cell uses another distribution or arrival process yet, so there is none
(PERF.md lists the burst mix that would need a gamma gap).
"""

from __future__ import annotations

import math
import statistics

import numpy as np


def length_multiset(spec: dict) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    n = int(spec["count"])
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    values = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(values), spec["min"], spec["max"]).astype(np.int64)


def prompts(spec: dict, vocab_size: int, seed: int, *, first_id: int = 4):
    """The mix's prompts for this seed: a list of int arrays of content ids
    (no specials), the length multiset in seeded order."""
    rng = np.random.default_rng([int(seed), 1])
    lengths = rng.permutation(length_multiset(spec))
    return [
        rng.integers(first_id, vocab_size, size=int(n)).astype(np.int32)
        for n in lengths
    ]


def _gap_quantiles(spec: dict, n: int) -> np.ndarray:
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    q = (np.arange(n) + 0.5) / n
    rate = float(spec["rate_per_s"])
    gaps = -np.log1p(-q) / rate
    return gaps * (n / rate) / gaps.sum()


def due_times(spec: dict, seconds: float, seed: int) -> np.ndarray:
    """Due instants (seconds from the schedule's start) of every request
    due within ``seconds``: round(rate * seconds) of them."""
    n = max(int(round(float(spec["rate_per_s"]) * seconds)), 1)
    rng = np.random.default_rng([int(seed), 2])
    return np.cumsum(rng.permutation(_gap_quantiles(spec, n)))


def train_batches(spec: dict, cfg: dict, rows: int, seed: int, count: int):
    """``count`` full batches ``(src [rows, S], trg [rows, T + 1])`` of
    token ids drawn from the seed: every position a real token (no pad, no
    specials inside), so every target position counts and all rows differ."""
    rng = np.random.default_rng([int(seed), 3])
    s, t = int(spec["src_len"]), int(spec["trg_len"])
    out = []
    for _ in range(count):
        src = rng.integers(4, cfg["src_vocab_size"], (rows, s), dtype=np.int32)
        trg = rng.integers(4, cfg["trg_vocab_size"], (rows, t + 1), dtype=np.int32)
        src[:, 0], src[:, -1] = cfg["sos_id"], cfg["eos_id"]
        trg[:, 0], trg[:, -1] = cfg["sos_id"], cfg["eos_id"]
        out.append((src, trg))
    return out


def nearest_rank(values, pct: float) -> float:
    """The pct-th percentile by nearest rank (no interpolation)."""
    ordered = sorted(values)
    k = max(int(math.ceil(pct / 100.0 * len(ordered))), 1)
    return float(ordered[k - 1])
