"""Host time of ``api.run``'s stacking of the rounds' batches into one
pytree (``_stack_batches``) per federated round, in ms: the program's
span ``fedmm.run.stack``, read as its ``jax.monitoring`` duration. None
where the program records no such span."""

EVENT = "/fedmm/run/stack"


def read(ctx):
    w = ctx.window
    total = ctx.events.total.get(EVENT)
    if not w or not w.get("units") or total is None:
        return None
    return 1000.0 * total / w["units"]
