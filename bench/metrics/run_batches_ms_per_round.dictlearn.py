"""Host time of ``api.run``'s batch draws (the data callable, once per
round) per federated round, in ms: the program's span
``fedmm.run.batches``, read as its ``jax.monitoring`` duration. It holds
the benchmark's own span around the data callable. None where the
program records no such span."""

EVENT = "/fedmm/run/batches"


def read(ctx):
    w = ctx.window
    total = ctx.events.total.get(EVENT)
    if not w or not w.get("units") or total is None:
        return None
    return 1000.0 * total / w["units"]
