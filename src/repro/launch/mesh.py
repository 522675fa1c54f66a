"""Device meshes for the federated client stage.

``make_edge_mesh`` lays clients over an ``(edge, client)`` device grid for
two-tier aggregation; ``cohort_capacity`` says how many clients one
scheduler cohort streams through a mesh. Importing this module never
touches JAX device state.
"""
from __future__ import annotations

import numpy as np

import jax


def make_edge_mesh(n_edges: int, clients_per_edge: int = None, *,
                   edge_axis: str = "edge", client_axis: str = "client",
                   devices=None):
    """A 2-D ``(edge, client)`` mesh for two-tier aggregation.

    Device (e, c) hosts client block ``e * clients_per_edge + c``, so each
    edge owns a CONTIGUOUS block of the stacked client axis — the same
    edge-major order ``Topology.edge_ids`` assigns, which is what lets a
    within-edge psum over ``client_axis`` and a cross-edge psum over
    ``edge_axis`` reproduce the flat reduction (up to association).
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    if n_edges < 1:
        raise ValueError(f"n_edges must be >= 1, got {n_edges}")
    if clients_per_edge is None:
        if len(devices) % n_edges:
            raise ValueError(
                f"{len(devices)} devices do not split over n_edges="
                f"{n_edges}; pass clients_per_edge explicitly")
        clients_per_edge = len(devices) // n_edges
    if clients_per_edge < 1:
        raise ValueError(
            f"clients_per_edge must be >= 1, got {clients_per_edge}")
    if edge_axis == client_axis:
        raise ValueError(
            f"edge_axis and client_axis must differ, both {edge_axis!r}")
    n = n_edges * clients_per_edge
    if len(devices) < n:
        raise ValueError(
            f"two-tier mesh ({n_edges} edges x {clients_per_edge} clients) "
            f"needs {n} devices, have {len(devices)}")
    grid = np.asarray(devices[:n]).reshape(n_edges, clients_per_edge)
    axes = (edge_axis, client_axis)
    return jax.sharding.Mesh(
        grid, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def cohort_capacity(mesh, client_axis="clients", per_device: int = 1) -> int:
    """The cohort size a ``repro.sched.CohortScheduler`` should stream
    through ``mesh``: one client slot per device on the client axis times
    ``per_device`` (raise it when a single client's oracle underfills a
    device). This is the C that makes the shard_mapped client stage run
    with zero idle devices and device memory independent of the population
    size — the scheduler pads the last ragged cohort up to it.

    ``client_axis`` may be a tuple of axis names — e.g. the two-tier
    ``("edge", "client")`` layout — in which case the capacity is the
    product of the named axis sizes times ``per_device``.
    """
    axes = (client_axis,) if isinstance(client_axis, str) \
        else tuple(client_axis)
    if not axes:
        raise ValueError("client_axis must name at least one mesh axis")
    for ax in axes:
        if ax not in mesh.shape:
            raise ValueError(f"client_axis={ax!r} not an axis of "
                             f"the mesh (axes: {tuple(mesh.shape)})")
    if per_device < 1:
        raise ValueError(f"per_device must be >= 1, got {per_device}")
    cap = per_device
    for ax in axes:
        cap *= int(mesh.shape[ax])
    return cap
