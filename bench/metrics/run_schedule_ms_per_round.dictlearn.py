"""Host time of ``api.run``'s step-size schedule (``resolve_schedule``,
one gamma per round) per federated round, in ms: the program's span
``fedmm.run.schedule``, read as its ``jax.monitoring`` duration. None
where the program records no such span."""

EVENT = "/fedmm/run/schedule"


def read(ctx):
    w = ctx.window
    total = ctx.events.total.get(EVENT)
    if not w or not w.get("units") or total is None:
        return None
    return 1000.0 * total / w["units"]
