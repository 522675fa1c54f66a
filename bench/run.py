#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: its configuration
(``BENCHMARK.json`` -> ``bench/configs/<config>.json``), its traffic and
correctness limits (``bench/workloads/<cell>.json``, whose ``driver`` names
``bench/drivers/<driver>.py``), its plain reference
(``bench/reference/<config>.py``) and its per-layer metric readers
(``bench/metrics/<metric>.py``).

A run: set-up (inputs and weights from the seed, the cell's programs
compiled or loaded from the compile cache, the first rounds that the
reference will follow), a measured window of ``--seconds``, then the
correctness check against the reference once the program's state is freed.
``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` takes a
profiler trace of the window and prints the per-layer metrics. The last
line of standard output is one JSON object; the numbers compared for
``correct`` end standard error and the JSON line, each beside its limit.

Without a TPU, with fewer chips than the cell needs, or outside a checkout
of the system under test, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _by_name(items, name):
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(name)


class Context:
    """What a driver and a metric reader see of the run."""

    def __init__(self, *, cell, config, workload, seed, devices, spans,
                 events, bench=BENCH):
        self.cell, self.config, self.workload = cell, config, workload
        self.seed, self.devices = seed, devices
        self.spans, self.events, self.bench = spans, events, bench
        self.window = None      # the window's own numbers
        self.trace = None       # the reduced trace (traced runs)

    def load_reference(self, config_name: str):
        return load_module(os.path.join(self.bench, "reference",
                                        f"{config_name}.py"),
                           f"bench_reference_{config_name.replace('-', '_')}")

    def load_flops(self, config_name: str):
        return load_module(os.path.join(self.bench, "flops",
                                        f"{config_name}.py"),
                           f"bench_flops_{config_name.replace('-', '_')}")

    def peaks(self) -> dict:
        from bench.peaks import peaks_for
        return peaks_for(self.devices[0].device_kind)


def per_layer_metrics(manifest: dict, cell: str) -> list:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    reported = {m["name"] for m in manifest["end_to_end"]
                if cell in m.get("workloads", [cell])}
    return [m for m in manifest["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, manifest: dict = None, config: dict = None,
             workload: dict = None, require_chip: bool = True,
             compile_cache: bool = True, t_start: float = None,
             out=None, err=None) -> int:
    """One run of ``cell``; prints the result and returns the exit code.
    Tests pass ``manifest``/``config``/``workload`` and
    ``require_chip=False`` to drive a tiny cell on the CPU."""
    out = out or sys.stdout
    err = err or sys.stderr
    t_start = T_PROCESS if t_start is None else t_start
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        print(f"bench: no system under test at {root}/src/repro", file=err)
        return 2
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)
    if manifest is None:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            manifest = json.load(f)
    entry = _by_name(manifest["workloads"], cell)
    if config is None:
        with open(os.path.join(root, _by_name(manifest["configs"],
                                              entry["config"])["file"])) as f:
            config = json.load(f)
    if workload is None:
        with open(os.path.join(root, "bench", "workloads",
                               f"{cell}.json")) as f:
            workload = json.load(f)

    import jax

    devices = jax.devices()
    chips = entry["chips"]
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < chips):
        print(f"bench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=err)
        return 1
    devices = devices[:chips]
    if compile_cache:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(root, ".jax_cache"))
        # every program goes to the cache, so that only a checkout's first
        # run compiles and nothing compiles inside a window
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import compare
    from bench.spans import Events, Spans

    spans, events = Spans(), Events()
    ctx = Context(cell=cell, config=config, workload=workload, seed=seed,
                  devices=devices, spans=spans, events=events,
                  bench=os.path.join(root, "bench"))
    driver = load_module(os.path.join(root, "bench", "drivers",
                                      f"{workload['driver']}.py"),
                         f"bench_driver_{workload['driver']}")
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {}}
    readings, note, c = {}, None, None
    with jax.default_device(devices[0]):
        try:
            c = driver.Cell(ctx)
            c.setup()
            setup_s = time.time() - t_start
            spans.reset()
            events.reset()
            events.on = True
            if trace:
                w, ctx.trace = _traced_window(c, ctx, seconds)
            else:
                w = c.window(seconds)
            events.on = False
            ctx.window = w
            result["attempted"], result["failed"] = w["attempted"], \
                w["failed"]
            compiles = events.count.get(
                "/jax/compilation_cache/cache_misses", 0)
            if compiles:
                note = f"{compiles} program(s) compiled inside the window"
            peak = _memory_peak(devices)
            c.release()
            gc.collect()
            readings = c.check()
        except Exception:
            traceback.print_exc(file=err)
            result["failed"] = max(result["failed"], 1)
            w, peak = None, _memory_peak(devices)
    checks = compare.verdict(readings, workload["limits"])
    result["correct"] = bool(w is not None and result["failed"] == 0
                             and all(v <= lim for _, v, lim in checks))
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]
             + manifest["per_layer"]}
    if w is not None and not trace:
        for name, v in w["e2e"].items():
            result["metrics"][name] = {"value": v, "unit": units[name]}
        result["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
    if w is not None and trace:
        for m in per_layer_metrics(manifest, cell):
            reader = load_module(
                os.path.join(root, "bench", "metrics", f"{m['name']}.py"),
                "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
    dev = devices[0]
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices), "memory_peak_bytes": peak}
    if trace and ctx.trace:
        result["device"]["busy_s"] = ctx.trace["busy_s"]
        result["device"]["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in checks}
    if note:
        print(f"bench: {note}", file=err)
    for line in getattr(c, "notes", []):
        print(f"bench: {line}", file=err)
    for k, v, lim in checks:
        print(f"check {k}: {v!r} (limit {lim!r})", file=err)
    err.flush()
    print(json.dumps(result, allow_nan=True), file=out)
    out.flush()
    return 0


def _traced_window(c, ctx, seconds):
    import jax

    from bench import trace_reduce
    traced = min(seconds, ctx.workload.get("trace_seconds", seconds))
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        ctx.spans.annotate = True
        with jax.profiler.trace(d, profiler_options=trace_reduce.options()):
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                w = c.window(traced)
        ctx.spans.annotate = False
        summary = trace_reduce.reduce_dir(d)
    return w, summary


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if not os.path.isfile(os.path.join(ROOT, "BENCHMARK.json")):
        print("bench: no BENCHMARK.json", file=sys.stderr)
        return 2
    return run_cell(a.workload, a.seed, a.seconds, bool(a.trace))


if __name__ == "__main__":
    sys.exit(main())
