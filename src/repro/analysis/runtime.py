"""Layer 3 — the checkify runtime sanitizer behind
``api.run/step(..., sanitize=True)``.

Two independent guards, both OFF by default and zero-cost when off (the
driver takes a plain ``if sanitize:`` branch around them):

* **checkify** — ``checkified(fn)`` functionalizes
  ``jax.experimental.checkify`` NaN / division-by-zero / out-of-bounds
  checks through the driver's scan (and vmap'd client stage): the checks
  ride the trace, so a NaN produced in round 37 of a 200-round scanned
  trajectory surfaces with its origin instead of as a silently poisoned
  iterate. The transform only ADDS error-tracking outputs — the primal
  computation is untouched, which is why the pinned golden trajectories
  stay bit-identical under ``sanitize=True``
  (tests/test_sanitizer.py pins this).
* **comm-bytes audit** — ``assert_comm_audit`` cross-checks the analytic
  ``Compressor.payload_bytes`` model against the bytes MEASURED off the
  actual encoded buffers at trace time. The PR-3 contract ("the metric is
  the wire") is otherwise only enforced in tests; under ``sanitize=True``
  every driver round re-proves it for the live spec.
"""
from __future__ import annotations

from typing import Optional


def default_errors():
    """NaN + div-by-zero + OOB-index — the sanitizer's error set."""
    from jax.experimental import checkify
    return checkify.nan_checks | checkify.div_checks | checkify.index_checks


_SHARD_MAP_RULE_PATCHED = False


def _collapse_error_device_axis(error):
    """Collapse the per-device leading axis jax's shard_map checkify rule
    leaves on every error leaf: the rule expands each error
    value to shape (axis_size, ...) and never reduces it back, so the
    very next checked op after a shard_map dies in a select between the
    ambient scalar error and the (axis_size,)-shaped one. Reduce it here:
    pred -> any over devices, code/payload -> the FIRST tripped device's
    (argmax of a bool vector is the first True; device 0's no-error code
    when nothing tripped, which merges as no-error)."""
    import jax.numpy as jnp
    from jax._src.checkify import Error

    pred, code, payload = {}, {}, {}
    for k, p in error._pred.items():
        if getattr(p, "ndim", 0) >= 1:
            i = jnp.argmax(p, axis=0)
            pred[k] = jnp.any(p, axis=0)
            code[k] = error._code[k][i]
            # the payload is a flat LIST of arrays (the exception's
            # flattened pytree), each carrying the device axis
            payload[k] = [arr[i] for arr in error._payload[k]]
        else:
            pred[k] = p
            code[k] = error._code[k]
            payload[k] = error._payload[k]
    return Error(pred, code, error._metadata, payload)


def _patch_shard_map_checkify_rule():
    """Make checkify compose with shard_map on this jax version.

    jax 0.9's ``shard_map_error_check`` (``jax._src.checkify``) returns
    the error with a leading device axis (it lax.expand_dims's every
    error leaf and shards the output over the whole mesh) — correct
    inside the shard_map, but the interpreter threads that shaped error
    on as the ambient state and the next join (a select, or a loop carry
    such as the driver's fixed-order client reduce) fails with "select
    cases must have the same shapes". Wrap the registered rule to
    collapse the device axis on the way out. Idempotent; scalar error
    leaves pass through untouched."""
    global _SHARD_MAP_RULE_PATCHED
    if _SHARD_MAP_RULE_PATCHED:
        return
    import jax._src.checkify as cki
    from jax._src import shard_map as _sm
    orig = cki.error_checks[_sm.shard_map_p]

    def rule_with_scalar_error(error, enabled_errors, *vals, **params):
        new_error, outs = orig(error, enabled_errors, *vals, **params)
        try:
            new_error = _collapse_error_device_axis(new_error)
        except Exception:
            # the collapse pokes at jax._src.checkify.Error internals
            # (_pred/_code/_metadata/_payload, positional ctor) — if a jax
            # upgrade reshuffles that layout, degrade to the upstream
            # rule's (device-shaped) error instead of crashing the trace
            pass
        return new_error, outs

    cki.error_checks[_sm.shard_map_p] = rule_with_scalar_error
    _SHARD_MAP_RULE_PATCHED = True


def checkified(fn, errors=None):
    """``checkify.checkify(fn)`` with the sanitizer's default error set.
    Returns ``g`` with ``err, out = g(*args)``; call ``err.throw()``
    EAGERLY (outside any jit) to raise on the first tripped check."""
    from jax.experimental import checkify
    _patch_shard_map_checkify_rule()
    return checkify.checkify(
        fn, errors=default_errors() if errors is None else errors)


def assert_comm_audit(comp, model_tree, measured_per_client: Optional[float],
                      *, where: str, tol: float = 0.5):
    """The comm-bytes audit: the analytic ``payload_bytes`` model must
    equal the measured per-client wire bytes (read off the actual encoded
    buffers / their eval_shape). Both are trace-time Python floats —
    shapes are static under jit — so a lying model fails fast with a
    diagnosable error instead of corrupting ``comm_bytes`` metrics.
    ``tol`` absorbs sub-byte float representation only."""
    if measured_per_client is None:
        return
    expected = float(comp.payload_bytes(model_tree))
    if abs(float(measured_per_client) - expected) > tol:
        raise ValueError(
            f"comm-bytes audit failed ({where}): Compressor "
            f"'{getattr(comp, 'name', comp)}' bills "
            f"payload_bytes={expected:.1f} B/client but the wire "
            f"measured {float(measured_per_client):.1f} B/client — the "
            f"analytic model and the encoded buffers disagree, so the "
            f"comm_bytes metric is lying (see "
            f"analysis.contracts.check_compressor contract 3)")
