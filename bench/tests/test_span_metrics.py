"""The per-layer metrics that read the program's host spans inside
``api.run`` (``bench/metrics/run_*.dictlearn.py``): by hand on a made-up
context, and from a traced tiny run on the CPU."""
import io
import json
import os
import types

import pytest

from bench import run

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
ROOT = os.path.dirname(os.path.dirname(HERE))
METRICS = os.path.join(ROOT, "bench", "metrics")
CELL = "dictlearn-movielens.run300"

PER_ROUND = ("keys", "schedule", "batches", "stack")
# 2 calls of 300 rounds; seconds per span over the window
TOTALS = {"/fedmm/run": 4.4, "/fedmm/run/keys": 0.3,
          "/fedmm/run/schedule": 0.6, "/fedmm/run/batches": 1.2,
          "/fedmm/run/stack": 0.5, "/fedmm/run/scan": 1.6}
EXPECTED = {
    "run_keys_ms_per_round.dictlearn": 0.5,
    "run_schedule_ms_per_round.dictlearn": 1.0,
    "run_batches_ms_per_round.dictlearn": 2.0,
    "run_stack_ms_per_round.dictlearn": 5.0 / 6.0,
    "run_scan_ms_per_call.dictlearn": 800.0,
    "run_self_ms_per_call.dictlearn": 100.0,
}
SPAN_OF = {name: "/fedmm/run" + (
    "" if "self" in name else "/" + name.split("_")[1]) for name in EXPECTED}


def _reader(name):
    return run.load_module(os.path.join(METRICS, f"{name}.py"),
                           "test_metric_" + name.replace(".", "_"))


def _ctx(totals):
    return types.SimpleNamespace(
        window={"units": 600, "calls": 2, "elapsed": 4.5},
        events=types.SimpleNamespace(total=dict(totals)))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_by_hand(name):
    assert _reader(name).read(_ctx(TOTALS)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_without_its_span_reads_nothing(name):
    totals = {k: v for k, v in TOTALS.items() if k != SPAN_OF[name]}
    assert _reader(name).read(_ctx(totals)) is None
    # a program with no spans at all, as before they were added
    assert _reader(name).read(_ctx({})) is None


def test_traced_tiny_run_reports_every_span_metric():
    def load(name):
        with open(os.path.join(DATA, name)) as f:
            return json.load(f)

    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell(CELL, 2 ** 33 + 12345, 0.5, True, root=ROOT,
                      config=load("tiny-dictlearn.json"),
                      workload=load("tiny-dictlearn.workload.json"),
                      require_chip=False, compile_cache=False, out=out,
                      err=err)
    assert rc == 0, err.getvalue()
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    metrics = result["metrics"]
    for name in EXPECTED:
        assert metrics[name]["value"] > 0.0, (name, metrics)
        assert metrics[name]["unit"] == "ms"
    # the program's batch span holds the benchmark's span of the callable
    assert (metrics["run_batches_ms_per_round.dictlearn"]["value"]
            >= metrics["host_batch_ms_per_round.dictlearn"]["value"])
