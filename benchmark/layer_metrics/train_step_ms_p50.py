"""Median over the loop's own log laps in the window of seconds a step, in ms
(a lap ends in a device sync; the ``train.step`` span only times the dispatch)."""

from benchmark.kinds import train


def read(run):
    return train.summarize_laps(run)
