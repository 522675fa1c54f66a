"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy time, the operation table, idle gaps labelled by what the
host was doing, and collective time with its exposed part.

Device planes are the ``/device:TPU:<n>`` planes; their operations are the
events of the ``XLA Ops`` line. The traced window is the host span
``bench.window`` that ``bench/run.py`` opens around the measured loop; the
host line that holds it also holds the benchmark's own spans
(``bench.<name>``) and JAX's host events, and each moment of an idle gap
on a device goes to the innermost of those running then.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)


def options():
    """Profiler options for a benchmark trace: host events and device
    operations, without the Python function tracer (whose events would
    cost the traced window time and bury the benchmark's spans)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """Disjoint sorted intervals ``a`` minus the union ``b``."""
    out, j = [], 0
    b = union(b)
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _events(line, short=False):
    """(name, start_ns, end_ns) of a line's events; ``short`` cuts a device
    operation's HLO text (``%fusion.3 = f32[...] ...``) to its name."""
    out = []
    for ev in line.events:
        name = ev.name
        if short:
            name = name.split(" = ", 1)[0].lstrip("%")
        out.append((name, float(ev.start_ns),
                    float(ev.start_ns + ev.duration_ns)))
    return out


def load(path: str) -> dict:
    """Planes of interest from an xplane file: ``{"devices": {id: [(name,
    start, end)]}, "host": [(name, start, end)]}`` with the host line that
    holds the ``bench.window`` span (times in ns, one clock)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(_events(line, short=True))
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                if any(n == WINDOW for n, _, _ in evs):
                    host = evs
    return {"devices": devices, "host": host}


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and idle time on each device inside the traced window, the
    operations that took most time (self time, averaged over the
    devices), the longest idle gaps by host activity, and collective time
    (total and exposed)."""
    win = [(s, e) for n, s, e in trace["host"] if n == WINDOW]
    if not win or not trace["devices"]:
        return {}
    lo, hi = win[0]
    host = [(n, s, e) for n, s, e in trace["host"] if n != WINDOW
            and e > lo and s < hi]
    host_segments = _segments(host)
    op_ns = defaultdict(float)
    gap_ns = defaultdict(float)
    busy, coll, exposed = [], [], []
    for dev, ops in sorted(trace["devices"].items()):
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if min(e, hi) > max(s, lo)]
        for n, t in _self_times(inside):
            op_ns[n] += t
        u = union([(s, e) for _, s, e in inside])
        busy.append(length(u))
        c = union([(s, e) for n, s, e in inside if COLLECTIVE.search(n)])
        other = [(s, e) for n, s, e in inside if not COLLECTIVE.search(n)]
        coll.append(length(c))
        exposed.append(length(subtract(c, other)))
        for name, t in _attribute(subtract([(lo, hi)], u), host_segments):
            gap_ns[name] += t
    n_dev = len(busy)
    ops_sorted = sorted(op_ns.items(), key=lambda kv: -kv[1])
    gaps_sorted = sorted(gap_ns.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "devices": n_dev,
        "collective_s": sum(coll) / n_dev * 1e-9,
        "collective_exposed_s": sum(exposed) / n_dev * 1e-9,
        "device_ops": [[n, v / n_dev * 1e-9] for n, v in ops_sorted[:top]],
        "idle_gaps": [[n, v / n_dev * 1e-9] for n, v in gaps_sorted[:top]],
    }


def _self_times(ops):
    """(name, self time) of each operation: its duration less that of the
    operations nested in it (a loop's body runs inside the loop's own
    event on the same line)."""
    ops = sorted(ops, key=lambda x: (x[1], -x[2]))
    out, stack = [], []           # stack of [name, start, end, child time]
    for n, s, e in ops:
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out.append((top[0], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += e - s
        stack.append([n, s, e, 0.0])
    out.extend((n, e - s - c) for n, s, e, c in stack)
    return out


def _segments(host):
    """The host's time cut into (start, end, name) pieces, each named by
    the innermost host event running then (events of one thread nest)."""
    segs, stack, t = [], [], None

    def advance(x):
        nonlocal t
        while stack and stack[-1][2] <= x:
            n, _, e = stack.pop()
            if e > t:
                segs.append((t, e, n))
                t = e
        if stack and x > t:
            segs.append((t, x, stack[-1][0]))
        t = max(t, x)

    for n, s, e in sorted(host, key=lambda x: (x[1], -x[2])):
        if t is None:
            t = s
        advance(s)
        stack.append((n, s, e))
    if stack:
        advance(max(e for _, _, e in stack))
    return segs


def _attribute(gaps, segs):
    """(name, time) pieces of the sorted ``gaps`` by the host segment they
    fall in; time in no segment goes to ``host (no span)``."""
    out, j = [], 0
    for s, e in gaps:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(segs) and segs[k][0] < e:
            a, b, n = segs[k]
            if a > cur:
                out.append(("host (no span)", a - cur))
            out.append((n, min(b, e) - max(a, cur)))
            cur = min(b, e)
            k += 1
        if cur < e:
            out.append(("host (no span)", e - cur))
    return out


def reduce_dir(trace_dir: str, top: int = 10) -> dict:
    return reduce(load(find_xplane(trace_dir)), top=top)
