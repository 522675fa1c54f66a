"""Host time of ``api.run``'s eager ``jax.lax.scan`` call per call, in ms:
tracing the scanned rounds, lowering, the compile-cache load and the
enqueue. The program's span ``fedmm.run.scan``, read as its
``jax.monitoring`` duration. None where the program records no such span."""

EVENT = "/fedmm/run/scan"


def read(ctx):
    w = ctx.window
    total = ctx.events.total.get(EVENT)
    if not w or not w.get("calls") or total is None:
        return None
    return 1000.0 * total / w["calls"]
