"""Toy sizes for a CPU rehearsal and for the tests: the same files, cut so
that a run takes seconds. A rehearsal proves paths, counts and the decision
of ``correct``; it is never a measurement, and its result line names the
platform it ran on. The widths are cut here; what belongs to one kind of
cell (its mix, its engine, its sample) is cut by that kind's own ``toy``,
so a new kind brings its own. The limits here are the toy's (a toy's leaves
are a few dozen numbers, so its norms are noisier than the real widths');
the cells' limits are in their files and were set on the chip.
"""

from __future__ import annotations

import copy

from benchmark import manifest

TOY_CONFIG = dict(
    src_vocab_size=64, trg_vocab_size=80, d_model=32, ffn_hidden=64,
    num_heads=4, max_len=32,
)
TOY_LIMITS = dict(
    loss1_rel=0.02, loss2_rel=0.02, loss3_rel=0.02,
    grad1_worst_leaf=0.15, grad1_median_leaf=0.03, change3_worst_leaf=0.15,
    served_gap_max=0.25, served_gap_mean=0.02, served_len_short=0,
)


def shrink(cfg: dict, mix: dict, cell_file: dict):
    cfg, mix, cell_file = map(copy.deepcopy, (cfg, mix, cell_file))
    cfg.update(TOY_CONFIG)
    cfg["num_layers"] = min(cfg["num_layers"], 2)
    manifest.load_kind(cell_file["kind"]).toy(cfg, mix, cell_file)
    cell_file["limits"] = {
        k: TOY_LIMITS[k]
        for k, limit in cell_file["limits"].items()
        if limit is not None and k in TOY_LIMITS
    }
    return cfg, mix, cell_file
