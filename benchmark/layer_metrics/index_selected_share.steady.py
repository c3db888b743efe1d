"""Positions a layer's attention read over the positions its row's context
held, the mean over every row, step and layer of the window's launches:
the token indexer's ``min(index_topk, t + 1)`` over ``t + 1``, counted on the
device from the selections the launch made (``models.dsa_lm.selected_share``,
summed into the runtime's ``selected_share_sum`` / ``selected_share_n``).

The same counter and reader as ``kv_selected_share.steady``, under a name of
its own: that metric's list of cells is held to the sala cell by its test."""

from benchmark import manifest

read = manifest.load_reader("kv_selected_share.steady")
