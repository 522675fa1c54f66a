"""JAX front-end time per ``api.run`` call, in ms: the durations JAX
reports through ``jax.monitoring`` for tracing to a jaxpr, lowering to an
MLIR module, and backend compile (which holds the compile-cache load)."""

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


def read(ctx):
    w = ctx.window
    if not w or not w.get("calls"):
        return None
    total = sum(ctx.events.total.get(e, 0.0) for e in EVENTS)
    if total <= 0:
        return None
    return 1000.0 * total / w["calls"]
