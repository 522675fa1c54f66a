"""Model FLOP/s utilization of the training step: decoder tokens per second
over the window, times the step's operations per token
(``bench/flops/<config>.py``), over the chips' bf16 peak
(``bench/peaks.json``), in percent."""


def read(ctx):
    w = ctx.window
    if not w or not w.get("tokens"):
        return None
    per_token = ctx.load_flops(ctx.config["name"]).train_flops_per_token(
        ctx.config, ctx.workload["seq_len"])
    peak = ctx.peaks()["bf16_flops_per_s"] * len(ctx.devices)
    return 100.0 * w["tokens"] / w["elapsed"] * per_token / peak
