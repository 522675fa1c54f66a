"""Device busy time in the traced window per federated round, in ms."""


def read(ctx):
    t, w = ctx.trace, ctx.window
    if not t or not w or not w.get("units"):
        return None
    return 1000.0 * t["busy_s"] / w["units"]
