"""Host time of ``api.run`` outside its five child phases per call, in
ms: the program's span ``fedmm.run`` less ``fedmm.run.keys``,
``.schedule``, ``.batches``, ``.stack`` and ``.scan`` (argument checks,
``init``, building the scan's inputs). None where the program records no
``fedmm.run`` span."""

EVENT = "/fedmm/run"
CHILDREN = ("keys", "schedule", "batches", "stack", "scan")


def read(ctx):
    w = ctx.window
    total = ctx.events.total.get(EVENT)
    if not w or not w.get("calls") or total is None:
        return None
    children = sum(ctx.events.total.get(f"{EVENT}/{c}", 0.0)
                   for c in CHILDREN)
    return 1000.0 * (total - children) / w["calls"]
