"""Host time of ``api.run``'s key chain (the per-round
``jax.random.split`` loop and the stack of round keys) per federated
round, in ms: the program's span ``fedmm.run.keys``, read as its
``jax.monitoring`` duration. None where the program records no such
span."""

EVENT = "/fedmm/run/keys"


def read(ctx):
    w = ctx.window
    total = ctx.events.total.get(EVENT)
    if not w or not w.get("units") or total is None:
        return None
    return 1000.0 * total / w["units"]
