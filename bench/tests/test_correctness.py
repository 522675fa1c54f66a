"""What decides ``correct``, driven end to end on the CPU at a tiny size.

A run of a tiny cell goes through the harness as a chip run does (set-up,
window, the reference once the window has closed) with the look for a
chip skipped. A sound run must come out correct; a run whose timed path is
broken underneath, once for each fault the cell can have, must come out
not correct; and the control (the reference one precision step lower, in
the program's place) must fail one of the cell's numbers. The limits of
the tiny cells are in ``data/tiny-*.workload.json``; the chip cells' own
limits and the readings behind them are in ``PERF.md``.
"""
import dataclasses
import io
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import control, run

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2 ** 33 + 12345

CELLS = {
    "whisper": ("whisper-base.logical4-b16", "tiny-whisper"),
    "dictlearn": ("dictlearn-movielens.run300", "tiny-dictlearn"),
}


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _manifest():
    """``BENCHMARK.json`` with the LM cell the tiny whisper runs stand for
    (its cells are out of the benchmark while the program is at fault on
    the chip; the harness still drives them)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = CELLS["whisper"][0]
    m["workloads"].append({"name": cell, "config": "whisper-base",
                           "traffic": "logical4-b16", "chips": 1,
                           "why": "test"})
    m["end_to_end"].append({"name": "tokens_per_s", "unit": "tokens/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock", "workloads": [cell]})
    return m


def _run(kind, seed=SEED):
    cell, tiny = CELLS[kind]
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell(cell, seed, 0.5, False, root=ROOT,
                      manifest=_manifest(), config=_load(f"{tiny}.json"),
                      workload=_load(f"{tiny}.workload.json"),
                      require_chip=False, compile_cache=False, out=out,
                      err=err)
    assert rc == 0, err.getvalue()
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    # every compared number ends stderr and the result line, beside its limit
    assert list(result)[-1] == "checks"
    assert err.getvalue().strip().splitlines()[-1].startswith("check ")
    return result


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_sound_run_is_correct(kind):
    r = _run(kind)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) >= {"setup_s"}


# -- faults planted in the timed path ----------------------------------------

def _state_unchanged_lm(monkeypatch):
    from repro.fed import trainer as FT
    orig = FT.make_train_step

    def broken(model, cfg, **kw):
        step = orig(model, cfg, **kw)

        def same_state(state, batch, key, gamma):
            return state, step(state, batch, key, gamma)[1]
        return same_state
    monkeypatch.setattr(FT, "make_train_step", broken)


def _half_batch_lm(monkeypatch):
    from repro.models import model as M
    orig = M.build_model

    def broken(cfg):
        m = orig(cfg)

        def loss_fn(params, batch):
            half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
            return m.loss_fn(params, half)
        return m._replace(loss_fn=loss_fn)
    monkeypatch.setattr(M, "build_model", broken)


def _state_unchanged_run(monkeypatch):
    from repro.api import driver as D
    orig = D._server_apply

    def broken(problem, spec, state, *a):
        new, h, aux = orig(problem, spec, state, *a)
        return state._replace(step=new.step), h, aux
    monkeypatch.setattr(D, "_server_apply", broken)


def _patch_oracle(monkeypatch, wrap):
    from repro.core import variational as V
    orig = V.make_dictlearn

    def broken(spec):
        sur = orig(spec)
        return dataclasses.replace(sur, s_bar=wrap(sur.s_bar))
    monkeypatch.setattr(V, "make_dictlearn", broken)


def _half_batch_run(monkeypatch):
    _patch_oracle(monkeypatch, lambda s_bar: lambda z, theta: s_bar(
        z[: z.shape[0] // 2], theta))


def _answer_altered_run(monkeypatch):
    def wrap(s_bar):
        def altered(z, theta):
            s = s_bar(z, theta)
            return dict(s, s2=s["s2"] * 1.01)
        return altered
    _patch_oracle(monkeypatch, wrap)


FAULTS = {
    "whisper-state_unchanged": ("whisper", _state_unchanged_lm),
    "whisper-half_batch": ("whisper", _half_batch_lm),
    "dictlearn-state_unchanged": ("dictlearn", _state_unchanged_run),
    "dictlearn-half_batch": ("dictlearn", _half_batch_run),
    "dictlearn-answer_altered": ("dictlearn", _answer_altered_run),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    kind, plant = FAULTS[fault]
    plant(monkeypatch)
    r = _run(kind)
    assert not r["correct"], r["checks"]


# -- the control and the faults planted in the reference ---------------------

@pytest.mark.parametrize("kind", sorted(CELLS))
def test_control_and_planted_faults_fail_a_number(kind):
    cell, tiny = CELLS[kind]
    limits = _load(f"{tiny}.workload.json")["limits"]
    readings = control.control_readings(
        cell, SEED, root=ROOT, manifest=_manifest(),
        config=_load(f"{tiny}.json"), workload=_load(f"{tiny}.workload.json"),
        require_chip=False)
    assert any(k.startswith("control") for k in readings)
    for variant, r in readings.items():
        failed = [k for k, lim in limits.items() if r[k] > lim]
        assert failed, (variant, r, limits)


# -- one silo per chip --------------------------------------------------------

PHYSICAL = """
import io, json, sys
sys.path[:0] = [{root!r}, {root!r} + "/src"]
from bench import run
man = json.load(open({root!r} + "/BENCHMARK.json"))
cell = {{"name": "tiny.physical4", "config": "whisper-base",
        "traffic": "physical4", "chips": 4, "why": "test"}}
man["workloads"].append(cell)
man["end_to_end"].append({{"name": "tokens_per_s", "unit": "tokens/s",
                          "better": "higher", "bound": 0.05,
                          "source": "host_clock",
                          "workloads": ["tiny.physical4"]}})
cfg = json.load(open({data!r} + "/tiny-whisper.json"))
wl = json.load(open({data!r} + "/tiny-whisper.workload.json"))
wl.update(client_mode="physical", uplink="reduce", n_clients=4,
          local_batch=2)
out, err = io.StringIO(), io.StringIO()
rc = run.run_cell("tiny.physical4", 7, 0.5, False, root={root!r},
                  manifest=man, config=cfg, workload=wl, require_chip=False,
                  compile_cache=False, out=out, err=err)
print(out.getvalue().strip().splitlines()[-1] if rc == 0 else err.getvalue())
"""


def test_one_silo_per_device_on_four_cpu_devices():
    """The four-chip path (one client per device on a ("clients",) mesh,
    ``uplink="reduce"``) on four CPU devices, in a process of its own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c",
                        PHYSICAL.format(root=ROOT, data=DATA)],
                       env=env, capture_output=True, text=True, timeout=900)
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"], r["checks"]


# -- no chip, no result -------------------------------------------------------

def test_without_a_chip_there_is_no_result():
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell("dictlearn-movielens.run300", 1, 1.0, False, root=ROOT,
                      out=out, err=err)
    assert rc != 0 and out.getvalue() == ""


def test_without_the_system_under_test_there_is_no_result(tmp_path):
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell("dictlearn-movielens.run300", 1, 1.0, False,
                      root=str(tmp_path), out=out, err=err)
    assert rc != 0 and out.getvalue() == ""
