"""Host time inside the benchmark's data callable (the per-round client
minibatch draws that ``api.run`` makes before stacking them for the
scan) per federated round, in ms, from the benchmark's own span."""


def read(ctx):
    w = ctx.window
    if not w or not w.get("units") or "data" not in ctx.spans.total:
        return None
    return 1000.0 * ctx.spans.total["data"] / w["units"]
