"""Key positions a sparse layer's step attended over the positions its
row's context held, the mean over every row and step of the window's launches
(counted on the device from the selections the launch made)."""


def read(run):
    n = run.counters.get("selected_share_n")
    return run.counters["selected_share_sum"] / n if n else None
