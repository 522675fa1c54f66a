"""``run_trace_share.dictlearn``, the share of scanned ``api.run`` calls
that traced their trajectory: by hand on a made-up context, and from a
traced tiny run on the CPU."""
import io
import json
import os
import types

import pytest

from bench import run

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = "run_trace_share.dictlearn"
CALL = "/fedmm/run/trajectory/call"
TRACE = "/fedmm/run/trajectory/trace"


def _reader():
    return run.load_module(os.path.join(ROOT, "bench", "metrics",
                                        f"{NAME}.py"),
                           "test_metric_" + NAME.replace(".", "_"))


def _ctx(count):
    return types.SimpleNamespace(
        window={"units": 600, "calls": 2, "elapsed": 4.5},
        events=types.SimpleNamespace(total={}, count=dict(count)))


@pytest.mark.parametrize("count, expected", [
    ({CALL: 2}, 0.0),
    ({CALL: 2, TRACE: 1}, 50.0),
    ({CALL: 2, TRACE: 2}, 100.0),
    ({}, None),
    ({TRACE: 1}, None),
], ids=["all-hit", "one-of-two", "all-traced", "no-events", "no-call"])
def test_reader_by_hand(count, expected):
    assert _reader().read(_ctx(count)) == expected


def test_traced_tiny_run_reads_no_trace():
    def load(name):
        with open(os.path.join(DATA, name)) as f:
            return json.load(f)

    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell("dictlearn-movielens.run300", 2 ** 33 + 12345, 0.5,
                      True, root=ROOT, config=load("tiny-dictlearn.json"),
                      workload=load("tiny-dictlearn.workload.json"),
                      require_chip=False, compile_cache=False, out=out,
                      err=err)
    assert rc == 0, err.getvalue()
    metrics = json.loads(out.getvalue().strip().splitlines()[-1])["metrics"]
    assert metrics[NAME] == {"value": 0.0, "unit": "%"}
