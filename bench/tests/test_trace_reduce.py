"""The reduction from a profiler trace to busy time, idle gaps by host
activity, the operation table and collective exposure."""
import os

import pytest

from bench import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "data", "small.xplane.pb")


def test_intervals():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 7), (6, 8)]) == \
        [(0, 2), (3, 5), (8, 10)]
    assert T.length([(0, 2), (5, 8)]) == 5


def test_self_time_of_nested_operations():
    ops = [("while.1", 0, 100), ("fusion.2", 10, 30), ("fusion.3", 40, 50),
           ("copy.4", 120, 125)]
    assert sorted(T._self_times(ops)) == [
        ("copy.4", 5), ("fusion.2", 20), ("fusion.3", 10), ("while.1", 70)]


def test_reduce_on_a_hand_made_trace():
    # window 0..100 ns on two devices; the host sits in bench.batch during
    # 0..30 and in bench.loss_read (inside bench.dispatch) during 62..100
    host = [("bench.window", 0, 100), ("bench.batch", 0, 30),
            ("bench.dispatch", 50, 100), ("bench.loss_read", 62, 100)]
    dev0 = [("fusion.1", 10, 20), ("all-reduce.2", 30, 40),
            ("fusion.3", 40, 50), ("all-reduce.2", 42, 44),
            ("fusion.1", 70, 80)]
    dev1 = [("fusion.1", 0, 100)]
    r = T.reduce({"devices": {0: dev0, 1: dev1}, "host": host})
    assert r["window_s"] == pytest.approx(100e-9)
    # device 0 busy 10 + 20 + 10 = 40, device 1 busy 100: mean 70
    assert r["busy_s"] == pytest.approx(70e-9)
    assert r["devices"] == 2
    # the all-reduce runs 30..40 alone and 42..44 under fusion.3
    assert r["collective_s"] == pytest.approx(6e-9)
    assert r["collective_exposed_s"] == pytest.approx(5e-9)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx((10 + 10 + 100) / 2 * 1e-9)
    gaps = dict(r["idle_gaps"])
    # device 0 idles 0..10 and 20..30 (batch), 50..62 (dispatch), 62..70
    # and 80..100 (loss_read); device 1 never: halved over two devices
    assert gaps == pytest.approx({"bench.batch": 10e-9,
                                  "bench.dispatch": 6e-9,
                                  "bench.loss_read": 14e-9})


def test_host_time_goes_to_the_innermost_event():
    host = [("a", 0, 100), ("b", 10, 20), ("c", 30, 60), ("d", 40, 50),
            ("e", 120, 130)]
    assert T._segments(host) == [
        (0, 10, "a"), (10, 20, "b"), (20, 30, "a"), (30, 40, "c"),
        (40, 50, "d"), (50, 60, "c"), (60, 100, "a"), (120, 130, "e")]
    assert T._attribute([(5, 15), (95, 125)], T._segments(host)) == [
        ("a", 5), ("b", 5), ("a", 5), ("host (no span)", 20), ("e", 5)]


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e by ``data/record_trace.py``: five
    dispatches of a small program with 2 ms host sleeps between them."""
    t = T.load(SMALL)
    assert list(t["devices"]) == [0]
    assert any(n == T.WINDOW for n, _, _ in t["host"])
    r = T.reduce(t)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["collective_s"] == 0.0
    names = [n for n, _ in r["device_ops"]]
    assert names and all(" = " not in n for n in names)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    # five 2 ms sleeps show up as idle time on the device
    assert idle >= 0.009
