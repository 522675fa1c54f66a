#!/usr/bin/env python3
"""Readings of a cell's correctness control and planted faults.

    python3 bench/control.py --workload <cell> --seeds 11 12 13

For each seed: the cell's inputs are made as a run makes them, then the
reference is run three ways, each compared with the reference itself by
the cell's own comparison: one precision step below the configuration's,
in the program's place (the control), and with each fault planted that the
cell can have. A limit must sit above what sound runs of the program read
and below what the control reads; each fault has to fail one of the cell's
numbers. The benchmark's own runs never run this. One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control_readings(cell: str, seed: int, *, root: str = ROOT,
                     manifest=None, config=None, workload=None,
                     require_chip: bool = True) -> dict:
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run

    if manifest is None:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            manifest = json.load(f)
    entry = run._by_name(manifest["workloads"], cell)
    if config is None:
        with open(os.path.join(root, run._by_name(
                manifest["configs"], entry["config"])["file"])) as f:
            config = json.load(f)
    if workload is None:
        with open(os.path.join(root, "bench", "workloads",
                               f"{cell}.json")) as f:
            workload = json.load(f)
    import jax
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise SystemExit("bench/control.py: no TPU")
    from bench.spans import Spans
    ctx = run.Context(cell=cell, config=config, workload=workload, seed=seed,
                      devices=devices[:entry["chips"]], spans=Spans(),
                      events=None, bench=os.path.join(root, "bench"))
    driver = run.load_module(
        os.path.join(root, "bench", "drivers", f"{workload['driver']}.py"),
        f"bench_driver_{workload['driver']}")
    c = driver.Cell(ctx)
    with jax.default_device(devices[0]):
        c.prepare()
        return c.control_readings()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    for seed in a.seeds:
        r = control_readings(a.workload, seed)
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "readings": r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
