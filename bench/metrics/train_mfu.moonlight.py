"""Model FLOP/s utilization of the sparse-expert training step: the
operations of the window's rounds (``bench/flops/<config>.py``: the part
every token goes through, per token, and the held experts' part, per
assignment from the program's counter ``expert_load``) over the window's
time and the chips' bf16 peak (``bench/peaks.json``), in percent. None
where the window has no such counter."""
import numpy as np


def read(ctx):
    w = ctx.window
    if not w or not w.get("tokens") or w.get("expert_load") is None:
        return None
    flops = ctx.load_flops(ctx.config["name"]).train_flops(
        ctx.config, ctx.workload["seq_len"], w["tokens"],
        int(np.sum(w["expert_load"])))
    peak = ctx.peaks()["bf16_flops_per_s"] * len(ctx.devices)
    return 100.0 * flops / w["elapsed"] / peak
