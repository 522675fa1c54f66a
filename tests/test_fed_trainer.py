"""FedMM-at-LM-scale trainer (repro.fed.trainer): semantics checks on CPU
with reduced architectures."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.fed import trainer as FT
from repro.models.model import build_model, make_batch

KEY = jax.random.PRNGKey(0)


def _setup(arch="phi3-medium-14b", n_clients=2, **kw):
    cfg = C.get(arch).reduced()
    model = build_model(cfg)
    fcfg = FT.FedLMConfig(n_clients=n_clients, rho=0.05, weight_decay=0.1,
                          **kw)
    state = FT.init_state(model, KEY, fcfg)
    step = jax.jit(FT.make_train_step(model, fcfg))
    b = make_batch(KEY, cfg, batch_size=n_clients * 2, seq_len=16)
    batch = {k: v.reshape((n_clients, 2) + v.shape[1:]) for k, v in b.items()}
    return model, fcfg, state, step, batch


@pytest.mark.slow
def test_loss_decreases_over_rounds():
    model, fcfg, state, step, batch = _setup(p=1.0, alpha=0.0, quant_bits=0)
    losses = []
    for t in range(12):
        state, m = step(state, batch, jax.random.PRNGKey(t), 0.7)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.1


@pytest.mark.slow
def test_equals_prox_sgd_when_unfederated():
    """n=1 client, p=1, no quant, alpha=0, gamma=1: the FedMM-LM round is
    exactly one proximal-SGD step theta <- T(theta - rho grad) in the mirror
    domain (Section 2.3 correspondence)."""
    model, fcfg, state, step, batch = _setup(n_clients=1, p=1.0, alpha=0.0,
                                             quant_bits=0)
    theta0 = FT.T_map(state.s_hat, fcfg)
    g = jax.grad(lambda p: model.loss_fn(p, jax.tree.map(lambda x: x[0], batch)))(theta0)
    s_expect = jax.tree.map(lambda th, gg: th - fcfg.rho * gg, theta0, g)

    new_state, _ = step(state, batch, KEY, 1.0)
    for a, b in zip(jax.tree.leaves(new_state.s_hat), jax.tree.leaves(s_expect)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-3, atol=2e-3)


def test_quantization_preserves_convergence():
    model, fcfg, state, step, batch = _setup(p=1.0, alpha=0.0, quant_bits=8)
    losses = []
    for t in range(12):
        state, m = step(state, batch, jax.random.PRNGKey(t), 0.5)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.05
    assert np.isfinite(losses).all()
    # unified-compressor communication accounting is surfaced per round
    comp = FT.resolve_compressor(fcfg)
    assert float(m["comm_bytes"]) == pytest.approx(
        comp.payload_bytes(state.s_hat) * float(m["n_active"]))
    from repro.core.compression import effective_omega
    assert float(m["omega_eff"]) == pytest.approx(
        effective_omega(comp.omega, fcfg.p), rel=1e-6)


@pytest.mark.slow
def test_partial_participation_masks_clients():
    model, fcfg, state, step, batch = _setup(n_clients=4, p=0.5, alpha=0.1,
                                             quant_bits=0)
    actives = []
    for t in range(10):
        state, m = step(state, batch, jax.random.PRNGKey(t), 0.3)
        actives.append(float(m["n_active"]))
    assert 0.0 <= min(actives) and max(actives) <= 4.0
    assert 0.2 < np.mean(actives) / 4.0 < 0.85  # ~p on average (40 draws)


@pytest.mark.slow
def test_server_cv_equals_mean_of_client_cvs():
    """Proposition 5 at LM scale."""
    model, fcfg, state, step, batch = _setup(n_clients=3, p=0.5, alpha=0.3,
                                             quant_bits=8)
    for t in range(5):
        state, _ = step(state, batch, jax.random.PRNGKey(t), 0.3)
    for v, vi in zip(jax.tree.leaves(state.v), jax.tree.leaves(state.v_i)):
        np.testing.assert_allclose(np.asarray(v, np.float32),
                                   np.asarray(jnp.mean(vi, axis=0), np.float32),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_no_cv_mode_trains_and_drops_state():
    """use_cv=False (Theorem 1's alpha=0 regime): no V/V_i state, loss
    still decreases under full participation."""
    cfg = C.get("phi3-medium-14b").reduced()
    from repro.models.model import build_model
    model = build_model(cfg)
    fcfg = FT.FedLMConfig(n_clients=2, rho=0.05, use_cv=False, alpha=0.0)
    state = FT.init_state(model, KEY, fcfg)
    assert jax.tree.leaves(state.v) == [] and jax.tree.leaves(state.v_i) == []
    step = jax.jit(FT.make_train_step(model, fcfg))
    b = make_batch(KEY, cfg, 4, 16)
    batch = {k: v.reshape((2, 2) + v.shape[1:]) for k, v in b.items()}
    losses = []
    for t in range(8):
        state, m = step(state, batch, jax.random.PRNGKey(t), 0.7)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_int8_kv_cache_decode_close_to_bf16():
    """Quantized KV cache (perf lever): decode logits within quantization
    noise of the full-precision cache."""
    import dataclasses
    import numpy as np
    from repro.models.model import build_model
    cfg = C.get("phi3-medium-14b").reduced()
    m = build_model(cfg)
    m8 = build_model(dataclasses.replace(cfg, kv_dtype="int8"))
    S = 32
    params = m.init(KEY)
    batch = make_batch(KEY, cfg, 2, S + 1)
    bs = {k: v[:, :S] for k, v in batch.items()}
    _, c1 = m.prefill(params, bs, cache_len=S + 8)
    l1, _ = m.decode(params, c1, batch["tokens"][:, S:S + 1], jnp.asarray(S))
    _, c2 = m8.prefill(params, bs, cache_len=S + 8)
    l2, _ = m8.decode(params, c2, batch["tokens"][:, S:S + 1], jnp.asarray(S))
    d = np.abs(np.asarray(l1[..., :cfg.vocab]) - np.asarray(l2[..., :cfg.vocab]))
    assert float(d.max()) < 0.05
    # and the int8 cache really is int8
    assert c2[0]["k"].dtype == jnp.int8


# ---------------------------------------------------------------------------
# golden pin: the api.step-collapsed trainer vs the FROZEN pre-collapse
# hand-rolled client loop (PR 4). The frozen copy is the golden oracle —
# do not "simplify" it to call the new API.
# ---------------------------------------------------------------------------

def _legacy_make_train_step(model, cfg):
    """Verbatim semantics of the pre-PR-4 ``make_train_step`` (hand-rolled
    physical vmap / logical scan client loops)."""
    from repro import api

    spec = cfg.federation_spec()
    use_cv = spec.use_variates
    comp = spec.compressor

    def client_round(theta, s_hat, v_i_c, cb, qkey, active):
        loss, g = jax.value_and_grad(model.loss_fn)(theta, cb)
        if use_cv:
            d = jax.tree.map(
                lambda th, gg, s, vv: th - cfg.rho * gg.astype(th.dtype)
                - s - vv,
                theta, g, s_hat, v_i_c)
        else:
            d = jax.tree.map(
                lambda th, gg, s: th - cfg.rho * gg.astype(th.dtype) - s,
                theta, g, s_hat)
        if comp.encode is not None:
            q = comp.decode(comp.encode(qkey, d))
        else:
            q = comp.apply(qkey, d)
        q = jax.tree.map(lambda x: x * active.astype(x.dtype), q)
        if not use_cv:
            return loss, q, {}
        v_new = jax.tree.map(
            lambda v, dq: v + (spec.alpha / spec.participation) * dq,
            v_i_c, q)
        return loss, q, v_new

    def train_step(state, batch, key, gamma):
        n, p, alpha = spec.n_clients, spec.participation, spec.alpha
        theta = FT.T_map(state.s_hat, cfg)
        active, quant_keys = api.participation_draw(key, spec)
        active = active.astype(jnp.float32)

        if cfg.client_mode == "physical":
            losses, q, v_i_new = jax.vmap(
                client_round, in_axes=(None, None, 0, 0, 0, 0))(
                    theta, state.s_hat, state.v_i, batch, quant_keys, active)
            agg = jax.tree.map(lambda x: jnp.mean(x, axis=0), q)
        else:
            def body(carry, xs):
                agg_sum, loss_sum = carry
                cb, v_c, qk, act = xs
                loss, q_c, v_new = client_round(theta, state.s_hat, v_c,
                                                cb, qk, act)
                agg_sum = jax.tree.map(
                    lambda a, qq: a + qq.astype(a.dtype), agg_sum, q_c)
                return (agg_sum, loss_sum + loss), v_new

            zeros = jax.tree.map(
                lambda x: jnp.zeros(x.shape, x.dtype), state.s_hat)
            (agg_sum, loss_sum), v_i_new = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)),
                (batch, state.v_i, quant_keys, active))
            agg = jax.tree.map(lambda a: a / n, agg_sum)
            losses = loss_sum / n

        if use_cv:
            h = jax.tree.map(lambda vv, a: vv + a.astype(vv.dtype) / p,
                             state.v, agg)
            v_new = jax.tree.map(
                lambda vv, a: vv + ((alpha / p) * a).astype(vv.dtype),
                state.v, agg)
        else:
            h = jax.tree.map(lambda a: a / p, agg)
            v_new = state.v

        s_new = jax.tree.map(lambda s, hh: s + gamma * hh.astype(s.dtype),
                             state.s_hat, h)
        e_s = sum(jnp.sum(jnp.square(hh.astype(jnp.float32)))
                  for hh in jax.tree.leaves(h))
        comm = comp.round_metrics(state.s_hat, p=p)
        metrics = {"loss": jnp.mean(losses), "e_s": e_s,
                   "n_active": jnp.sum(active),
                   "comm_bytes": comp.wire_bytes(state.s_hat)
                   * jnp.sum(active),
                   "omega_eff": jnp.asarray(comm["omega_eff"], jnp.float32)}
        return FT.FedLMState(s_hat=s_new, v=v_new, v_i=v_i_new,
                             step=state.step + 1), metrics

    return train_step


@pytest.mark.parametrize("mode", ["physical", "logical"])
def test_collapsed_trainer_matches_frozen_legacy(mode):
    """The api.step round reproduces the hand-rolled loop's trajectory.
    (The server aggregation arithmetic changed shape — mu_i-weighted
    tensordot / scan accumulation instead of mean / sum-then-divide — so
    the pin is tight-allclose, not bit-exact; every other op is
    order-identical.)"""
    cfg = C.get("phi3-medium-14b").reduced()
    model = build_model(cfg)
    fcfg = FT.FedLMConfig(n_clients=2, rho=0.05, p=0.5, alpha=0.2,
                          quant_bits=8, client_mode=mode)
    state_new = FT.init_state(model, KEY, fcfg)
    state_old = FT.init_state(model, KEY, fcfg)
    step_new = jax.jit(FT.make_train_step(model, fcfg))
    step_old = jax.jit(_legacy_make_train_step(model, fcfg))
    b = make_batch(KEY, cfg, batch_size=4, seq_len=16)
    batch = {k: v.reshape((2, 2) + v.shape[1:]) for k, v in b.items()}
    for t in range(4):
        state_new, m_new = step_new(state_new, batch,
                                    jax.random.PRNGKey(t), 0.5)
        state_old, m_old = step_old(state_old, batch,
                                    jax.random.PRNGKey(t), 0.5)
        for k in ("loss", "e_s", "n_active", "comm_bytes", "omega_eff"):
            np.testing.assert_allclose(
                np.asarray(m_new[k]), np.asarray(m_old[k]),
                rtol=1e-5, atol=1e-6, err_msg=f"{mode} round {t}: {k}")
    for name, a, b_ in (("s_hat", state_new.s_hat, state_old.s_hat),
                        ("v", state_new.v, state_old.v),
                        ("v_i", state_new.v_i, state_old.v_i)):
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b_)):
            np.testing.assert_allclose(
                np.asarray(la, np.float32), np.asarray(lb, np.float32),
                rtol=1e-5, atol=1e-6, err_msg=f"{mode}: {name}")


def test_t_map_is_l2_prox():
    fcfg = FT.FedLMConfig(n_clients=1, rho=0.1, weight_decay=0.5)
    s = {"w": jnp.ones((3,))}
    out = FT.T_map(s, fcfg)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.ones(3) / (1 + 0.1 * 0.5), rtol=1e-6)


_SUBPROCESS_PHYSICAL_TWO_STEPS = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
import repro.configs as C
from repro.fed import trainer as FT
from repro.models.model import build_model, make_batch

assert jax.device_count() == 4, jax.device_count()
KEY = jax.random.PRNGKey(0)
cfg = C.get("whisper-base").reduced()
model = build_model(cfg)
n = 4
b = make_batch(KEY, cfg, batch_size=n, seq_len=16)
batch = {k: v.reshape((n, 1) + v.shape[1:]) for k, v in b.items()}
mesh = Mesh(np.asarray(jax.devices()), ("clients",))
losses = {}
for mode, kw in (("logical", {}), ("physical", dict(mesh=mesh,
                                                    uplink="reduce"))):
    fcfg = FT.FedLMConfig(n_clients=n, rho=0.05, client_mode=mode)
    state = FT.init_state(model, KEY, fcfg)
    step = jax.jit(FT.make_train_step(model, fcfg, **kw))
    out = []
    for t in range(2):       # step 2 takes the committed, sharded state
        state, m = step(state, batch, jax.random.PRNGKey(t), 0.5)
        out.append(float(m["loss"]))
    losses[mode] = out
np.testing.assert_allclose(losses["physical"], losses["logical"],
                           rtol=1e-5)
print("OK-PHYSICAL")
"""


def test_physical_mesh_trains_on_committed_state_under_forced_4_devices():
    """One silo per device, uplink='reduce': the second round takes the
    first round's mesh-sharded state. The shard_map body must get the
    server state as an argument — closing over it failed to
    differentiate the model's scanned loss on jax 0.9."""
    import os
    import subprocess
    import sys
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c",
                          _SUBPROCESS_PHYSICAL_TWO_STEPS],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK-PHYSICAL" in out.stdout
