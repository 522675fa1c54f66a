"""Imbalance of the held experts' load: for each round and MoE layer, the
largest held expert's assignments over the mean held expert's, averaged
over the window's rounds and layers (1 is even). Read from the program's
per-round counter ``expert_load`` (rounds, layers, held experts); None
where the window has none."""
import numpy as np


def read(ctx):
    w = ctx.window
    load = None if not w else w.get("expert_load")
    if load is None or np.size(load) == 0:
        return None
    load = np.asarray(load, np.float64)
    mean = load.mean(axis=-1)
    if not np.all(mean > 0):
        return None
    return float(np.mean(load.max(axis=-1) / mean))
