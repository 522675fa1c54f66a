"""Share of ``api.run``'s scanned federated calls that traced their
trajectory program, in %: 100 x the program's ``jax.monitoring`` events
``/fedmm/run/trajectory/trace`` over ``/fedmm/run/trajectory/call`` in the
window. 0 when every call reuses the kept program; None where the program
records no such call event (one that retraces every call and says
nothing)."""

CALL = "/fedmm/run/trajectory/call"
TRACE = "/fedmm/run/trajectory/trace"


def read(ctx):
    calls = ctx.events.count.get(CALL)
    if not calls:
        return None
    return 100.0 * ctx.events.count.get(TRACE, 0) / calls
