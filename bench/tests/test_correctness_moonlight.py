"""What decides ``correct`` in the sparse-expert LM cell, driven end to end
on the CPU at a tiny size (``data/tiny-moonlight*.json``), as
``test_correctness.py`` does for the other cells: a sound run is correct;
a timed path broken underneath is not; the control and the faults planted
in the reference each fail one of the cell's numbers; a round in which a
client's oracle output holds a NaN counts as failed."""
import io
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import control, run

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2 ** 33 + 12345
CELL = "moonlight-16b-a3b.fed2-8k"


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _run(seed=SEED, trace=False):
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell(CELL, seed, 0.5, trace, root=ROOT,
                      manifest=_manifest(),
                      config=_load("tiny-moonlight.json"),
                      workload=_load("tiny-moonlight.workload.json"),
                      require_chip=False, compile_cache=False, out=out,
                      err=err)
    assert rc == 0, err.getvalue()
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    return result


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"rounds_per_s", "setup_s"}


def test_traced_run_reports_the_cells_per_layer_metrics(monkeypatch):
    # the CPU has no entry in the peak table: a stand-in peak
    monkeypatch.setattr(run.Context, "peaks",
                        lambda self: {"bf16_flops_per_s": 1e12})
    r = _run(trace=True)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    # the device metrics read a device trace, which the CPU has not
    assert {"train_mfu.moonlight", "expert_load_imbalance.moonlight"} <= \
        set(m)
    assert m["expert_load_imbalance.moonlight"]["value"] >= 1.0
    assert m["train_mfu.moonlight"]["value"] > 0


def _state_unchanged(monkeypatch):
    from repro.fed import trainer as FT
    orig = FT.make_train_step

    def broken(model, cfg, **kw):
        step = orig(model, cfg, **kw)

        def same_state(state, batch, key, gamma):
            return state, step(state, batch, key, gamma)[1]
        return same_state
    monkeypatch.setattr(FT, "make_train_step", broken)


def _half_batch(monkeypatch):
    from repro.models import model as M
    orig = M.build_model

    def broken(cfg):
        m = orig(cfg)

        def loss_stats(params, batch):
            half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
            return m.loss_stats(params, half)
        return m._replace(loss_stats=loss_stats)
    monkeypatch.setattr(M, "build_model", broken)


def _experts_dropped(monkeypatch):
    """The held experts' part left out: only the shared experts run."""
    from repro.models import moe as MOE
    orig = MOE.moe_share_block

    def broken(params, cfg, x):
        y, counts = orig(dict(params, experts=jax.tree.map(
            jnp.zeros_like, params["experts"])), cfg, x)
        return y, counts
    monkeypatch.setattr(MOE, "moe_share_block", broken)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "experts_dropped": _experts_dropped}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    r = _run()
    assert not r["correct"], r["checks"]


def test_nonfinite_client_output_fails_the_run(monkeypatch):
    from repro.models import model as M
    orig = M.build_model

    def broken(cfg):
        m = orig(cfg)

        def loss_stats(params, batch):
            loss, stats = m.loss_stats(params, batch)
            return loss * jnp.where(batch["tokens"][0, 0] >= 0, jnp.nan,
                                    1.0), stats
        return m._replace(loss_stats=loss_stats)
    monkeypatch.setattr(M, "build_model", broken)
    r = _run()
    assert not r["correct"] and r["failed"] >= 1


def test_control_and_planted_faults_fail_a_number():
    limits = _load("tiny-moonlight.workload.json")["limits"]
    readings = control.control_readings(
        CELL, SEED, root=ROOT, manifest=_manifest(),
        config=_load("tiny-moonlight.json"),
        workload=_load("tiny-moonlight.workload.json"), require_chip=False)
    assert any(k.startswith("control") for k in readings)
    for variant, r in readings.items():
        failed = [k for k, lim in limits.items() if r[k] > lim]
        assert failed, (variant, r, limits)


def test_parent_like_window_reads_nothing():
    """The readers give None, and raise nothing, on a window without the
    program's expert counter (a program that lacks it)."""
    for name in ("train_mfu.moonlight", "expert_load_imbalance.moonlight",
                 "device_idle_share.moonlight",
                 "device_ms_per_round.moonlight"):
        reader = run.load_module(os.path.join(ROOT, "bench", "metrics",
                                              f"{name}.py"), name)
        ctx = run.Context(cell=CELL, config=_load("tiny-moonlight.json"),
                          workload=_load("tiny-moonlight.workload.json"),
                          seed=1, devices=jax.devices(), spans=None,
                          events=None)
        ctx.window = {"elapsed": 1.0, "tokens": 10, "attempted": 1}
        assert reader.read(ctx) is None
