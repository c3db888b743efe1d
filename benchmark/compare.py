"""The comparison that decides ``correct``.

Each number compared has a limit of its own, set from two readings (the
program's largest over many seeds, and the smallest that the control or a
planted fault gives): PERF.md gives both for every limit. The limits live
in the cell's file, not here.

Training numbers (program or a stand-in, against the reference):

- ``loss<k>_rel``: |loss - reference loss| / reference loss, steps 1-3;
- ``grad1_worst_leaf``: over the leaves, the gap between the norm of the
  first gradient as the optimizer got it and the reference's norm of that
  leaf, measured against the reference's norm of that leaf or of the median
  leaf, whichever is larger (some gradients are all but zero);
- ``change3_worst_leaf``: the same for the norm of the parameters' change
  after three steps. Leaves whose reference gradient is under a thousandth
  of the median leaf's are left out of this one: under Adam they move by
  round-off alone.

- ``grad1_median_leaf``, ``change3_median_leaf``: the median over the
  leaves of the same gaps: steady from seed to seed where the worst leaf is
  one small leaf's noise. Printed with every run; compared only where a
  cell's file gives them a limit.

Serving numbers: ``served_gap_max``, the widest gap by which a served
token's reference logit lies below the reference's best at its position;
``served_gap_mean``, the mean of that gap over every sampled served token
(nought where the token is the reference's best: it grows with the square
of the rounding, so it separates a precision that the widest gap, which
swings by its nature, does not); and ``served_len_short``, how many sampled
answers were shorter than the mix's fixed length (an exact comparison:
limit 0).

The control and the planted faults (``--control``) are the reference put in
the program's place; ``verdict`` puts their numbers through the same
``decide`` as the program's, so a stand-in that comes out correct shows.
"""

from __future__ import annotations

import statistics
import sys


def _leaf_gaps(program: dict, reference: dict, skip=()) -> dict[str, float]:
    median = statistics.median(reference.values())
    return {
        leaf: abs(program[leaf] - ref_norm) / max(ref_norm, median)
        for leaf, ref_norm in reference.items() if leaf not in skip
    }


def _worst_leaf(program: dict, reference: dict, skip=()) -> tuple[float, str]:
    gaps = _leaf_gaps(program, reference, skip)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def train_numbers(program: dict, reference: dict, limits: dict) -> list:
    out = []
    for k, (a, b) in enumerate(zip(program["losses"], reference["losses"]), 1):
        out.append((f"loss{k}_rel", abs(a - b) / abs(b)))
    grad_ref = reference["grad_norms"]
    worst, where = _worst_leaf(program["grad_norms"], grad_ref)
    out.append(("grad1_worst_leaf", worst, where))
    out.append(("grad1_median_leaf", statistics.median(
        _leaf_gaps(program["grad_norms"], grad_ref).values()
    )))
    floor = 1e-3 * statistics.median(grad_ref.values())
    still = {leaf for leaf, n in grad_ref.items() if n < floor}
    worst, where = _worst_leaf(
        program["change_norms"], reference["change_norms"], skip=still
    )
    out.append(("change3_worst_leaf", worst, where))
    out.append(("change3_median_leaf", statistics.median(_leaf_gaps(
        program["change_norms"], reference["change_norms"], skip=still
    ).values())))
    compared, printed = with_limits([row[:2] for row in out], limits)
    return compared, {row[0]: row[2] for row in out if len(row) > 2}, printed


def serve_numbers(gap_max: float, gap_mean: float, short: int, limits: dict):
    return with_limits([
        ("served_gap_max", gap_max), ("served_gap_mean", gap_mean),
        ("served_len_short", float(short)),
    ], limits)


def with_limits(numbers: list, limits: dict) -> tuple[list, dict]:
    """(name, value, limit) for the numbers that the cell's file gives a
    limit, and the rest by name: read and printed, not compared."""
    held = [(n, v, limits[n]) for n, v in numbers if limits.get(n) is not None]
    return held, {n: v for n, v in numbers if limits.get(n) is None}


def chosen(control, stand_ins) -> list[str]:
    """The stand-ins that ``--control`` names (``all``, or a list of names),
    in the kind's own order."""
    if "all" in control:
        return list(stand_ins)
    unknown = [name for name in control if name not in stand_ins]
    if unknown:
        raise KeyError(f"no stand-in {unknown}; this kind has {list(stand_ins)}")
    return [name for name in stand_ins if name in control]


def verdict(compared: list, printed: dict) -> dict:
    """A stand-in's numbers with what ``decide`` says of them."""
    return {
        "correct": decide(compared),
        "over": [name for name, value, limit in compared if not value <= limit],
        **{name: value for name, value, _ in compared},
        "not_compared": printed,
    }


def decide(compared: list) -> bool:
    """True where every number is within its limit (a NaN is not)."""
    return bool(compared) and all(
        value <= limit for _, value, limit in compared
    )


def report(compared: list) -> dict:
    """Print each number beside its limit (the run's last lines on standard
    error) and return the same as the result line's last key."""
    out = {}
    for name, value, limit in compared:
        verdict = "ok" if value <= limit else "OVER"
        print(f"compared {name} = {value:.6g} (limit {limit:g}) {verdict}",
              file=sys.stderr, flush=True)
        out[name] = {"value": value, "limit": limit}
    return out
