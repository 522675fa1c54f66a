"""Moonlight-16B-A3B (latent attention, a held share of sigmoid-routed
experts, shared experts, a leading dense layer) against its plain float32
reference (``bench/reference/moonlight-16b-a3b.py``) at a small size on
seeded random weights, and the pieces it brought: the attention backward
pass, the expert share routed without drops, and the trainer's per-round
expert counter."""
import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.fed import trainer as FT
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import transformer as T
from repro.models.model import build_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(ROOT, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("bench/reference/moonlight-16b-a3b.py", "moonlight_reference")
PRECISION = _load("bench/precision.py", "bench_precision")
DRIVER = _load("bench/drivers/fed_lm_moe.py", "fed_lm_moe_driver")
with open(os.path.join(ROOT, "bench/tests/data/tiny-moonlight.json")) as f:
    TINY = json.load(f)


def _model(config, dtype):
    cfg = dataclasses.replace(DRIVER.arch_config(config), dtype=dtype)
    return build_model(cfg)


def _params(model, seed):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return DRIVER.fed_lm.init_params(shapes, jax.random.PRNGKey(seed))


def _batch(key, vocab, b=2, s=32):
    toks = jax.random.randint(key, (b, s + 1), 0, vocab)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_config_is_published_moonlight():
    cfg = C.get("moonlight-16b-a3b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab) == \
        (27, 2048, 16, 163840)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.d_expert, cfg.n_shared_experts,
            cfg.d_ff, cfg.first_dense) == (64, 6, 1408, 2, 11264, 1)
    assert cfg.routed_scale == 2.446
    lead, rest = T.split_pattern(cfg)
    assert lead == ["mla_mlp"] and rest == ["mla_moe"] * 26


def test_benchmark_cut_has_every_published_width():
    with open(os.path.join(ROOT, "bench/configs/moonlight-16b-a3b.json")) as f:
        config = json.load(f)
    model = _model(config, "bfloat16")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == config["params"] == 568_484_352
    moe = shapes["stack"][0]["moe"]
    assert moe["router"].shape == (4, 2048, 64)         # routes over all 64
    assert moe["experts"]["w_gate"].shape == (4, 8, 2048, 1408)   # holds 8
    assert shapes["lead"][0]["mlp"]["w_gate"].shape == (1, 2048, 11264)


ROUTED = ("['router']", "['experts']")


@pytest.mark.parametrize("dtype,loss_tol,grad_tol,routed_tol",
                         [("float32", 2e-6, 2e-4, 2e-4),
                          ("bfloat16", 1e-3, 0.12, 0.35)])
def test_model_matches_reference_loss_and_grads(dtype, loss_tol, grad_tol,
                                                routed_tol):
    """Loss and every leaf's gradient of the program's model against the
    float32 reference on the same weights. float32: agreement to float
    rounding. bfloat16: the program's activations are rounded to bf16, so
    each leaf's gradient agrees to a few percent of its norm (3-8% here),
    and a token whose 4th and 5th expert scores nearly tie can pick another
    expert than the reference does, which moves the router's and the
    routed experts' gradients further (up to 22% on this seed)."""
    model = _model(TINY, dtype)
    params = _params(model, 7)
    batch = _batch(jax.random.PRNGKey(3), TINY["vocab_size"])
    loss, g = jax.value_and_grad(model.loss_fn)(params, batch)
    ref = REF.Moonlight(TINY, PRECISION.mm_highest)
    ntok = batch["tokens"].size
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    ref_loss, ref_g = jax.value_and_grad(
        lambda p: ref.token_ce_sum(p, batch["tokens"], batch["labels"])
        / ntok)(p32)
    assert abs(float(loss) - float(ref_loss)) <= loss_tol * float(ref_loss)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree.leaves(ref_g)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        gap = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)
        name = jax.tree_util.keystr(path)
        tol = routed_tol if any(r in name for r in ROUTED) else grad_tol
        assert gap <= tol, (name, gap)
        assert np.linalg.norm(a) > 0, jax.tree_util.keystr(path)


def _share_cfg(**kw):
    base = dict(d_model=32, n_experts=16, top_k=4, d_expert=24,
                n_shared_experts=2, routed_scale=2.446, moe_group=4,
                dtype="float32")
    base.update(kw)
    return dataclasses.replace(C.get("moonlight-16b-a3b"), **base)


def test_expert_shares_add_up_to_the_uncut_layer():
    """The 4 shares of 4 experts each, with the shared experts counted
    once, give what the uncut reference layer (all 16 experts held)
    gives."""
    full = _share_cfg()
    params = MOE.moe_share_init(jax.random.PRNGKey(0), full, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 32))
    total = -3.0 * L.mlp(params["shared"], x)          # counted 4 times
    counts = []
    for s in range(4):
        cfg = dataclasses.replace(full, experts_held=4, expert_base=4 * s)
        share = dict(params, experts=jax.tree.map(
            lambda w: w[4 * s:4 * s + 4], params["experts"]))
        y, c = MOE.moe_share_block(share, cfg, x)
        total = total + y
        counts.append(c)
    assert int(jnp.sum(jnp.stack(counts))) == 2 * 10 * 4   # every choice
    uncut = dict(TINY, hidden_size=32, n_routed_experts=16,
                 num_experts_per_tok=4)
    ref = REF.Moonlight(uncut, PRECISION.mm_highest).moe(params, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_dropless_under_skewed_routing():
    """A router that sends every token to the held experts: all T x k
    assignments land here, in several tiles per expert, and none is
    dropped: the layer equals the reference's dense mixture."""
    cfg = _share_cfg(experts_held=6, expert_base=0)
    params = MOE.moe_share_init(jax.random.PRNGKey(2), cfg, jnp.float32)
    # a constant input feature carries a large bias towards experts 0-5
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 32)).at[..., 0].set(
        1.0)
    params["router"] = params["router"].at[0].set(
        jnp.where(jnp.arange(16) < 6, 5.0, -5.0))
    y, counts = MOE.moe_share_block(params, cfg, x)
    assert int(jnp.sum(counts)) == 2 * 12 * 4
    assert int(jnp.max(counts)) > 2 * cfg.moe_group      # several tiles
    ref = REF.Moonlight(dict(TINY, hidden_size=32, n_routed_experts=6,
                             num_experts_per_tok=4),
                        PRECISION.mm_highest).moe(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def _naive_attention(q, k, v, causal):
    G = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, 2), jnp.repeat(v, G, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("causal,S,qb,kb", [(True, 37, 8, 16),
                                            (True, 64, 16, 16),
                                            (False, 29, 8, 8)])
def test_attention_backward_matches_naive_autodiff(causal, S, qb, kb):
    """qk width 24, v width 16, grouped heads, a sequence that pads the
    last blocks: the custom backward equals autodiff of a naive softmax
    attention."""
    ks = jax.random.split(jax.random.PRNGKey(S), 4)
    q = jax.random.normal(ks[0], (2, S, 4, 24))
    k = jax.random.normal(ks[1], (2, S, 2, 24))
    v = jax.random.normal(ks[2], (2, S, 2, 16))
    g = jax.random.normal(ks[3], (2, S, 4, 16))

    def blocked(q, k, v):
        return jnp.sum(L.blocked_attention(q, k, v, causal=causal,
                                           q_block=qb, kv_block=kb) * g)

    def naive(q, k, v):
        return jnp.sum(_naive_attention(q, k, v, causal) * g)

    np.testing.assert_allclose(float(blocked(q, k, v)),
                               float(naive(q, k, v)), rtol=1e-4)
    for a, b in zip(jax.grad(blocked, (0, 1, 2))(q, k, v),
                    jax.grad(naive, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_attention_backward_in_bf16_is_finite_and_close():
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k = (jax.random.normal(kk, (1, 48, 2, 24)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (1, 48, 2, 16))
    g = jax.random.normal(ks[3], (1, 48, 2, 16))

    def f(q, k, v, attn):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * g)

    def blocked(q, k, v):
        return L.blocked_attention(q, k, v, q_block=16, kv_block=16)

    gb = jax.grad(f, (0, 1, 2))(*(x.astype(jnp.bfloat16) for x in (q, k, v)),
                                blocked)
    gf = jax.grad(f, (0, 1, 2))(q, k, v,
                                lambda q, k, v: _naive_attention(q, k, v,
                                                                 True))
    for a, b in zip(gb, gf):
        a = np.asarray(a, np.float32)
        assert np.isfinite(a).all()
        assert np.linalg.norm(a - b) <= 0.03 * np.linalg.norm(b)


def test_train_step_counts_held_assignments():
    model = _model(TINY, "float32")
    fcfg = FT.FedLMConfig(n_clients=2, rho=0.05, quant_bits=8,
                          client_mode="logical")
    state = FT.init_state(model, jax.random.PRNGKey(0), fcfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    batch = jax.tree.map(lambda *x: jnp.stack(x),
                         _batch(k1, TINY["vocab_size"]),
                         _batch(k2, TINY["vocab_size"]))
    _, m = jax.jit(FT.make_train_step(model, fcfg))(
        state, batch, jax.random.PRNGKey(1), 0.5)
    load = np.asarray(m["expert_load"])
    assert load.shape == (2, 4) and load.dtype == np.int32
    # 2 clients x 2 x 32 tokens x 4 choices, a quarter of 16 experts held
    assert 0 < load.sum() <= 2 * 2 * 32 * 4
    assert int(m["n_nonfinite"]) == 0 and np.isfinite(float(m["loss"]))


def test_train_step_probes_the_mean_client_gradient():
    """``grad_probe`` is the all-client mean gradient at the coordinates
    the reference's ``probe`` reads, in float32."""
    model = _model(TINY, "float32")
    fcfg = FT.FedLMConfig(n_clients=2, rho=0.05, quant_bits=8,
                          client_mode="logical")
    state = FT.init_state(model, jax.random.PRNGKey(0), fcfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    b1, b2 = _batch(k1, TINY["vocab_size"]), _batch(k2, TINY["vocab_size"])
    batch = jax.tree.map(lambda *x: jnp.stack(x), b1, b2)
    _, m = jax.jit(FT.make_train_step(model, fcfg))(
        state, batch, jax.random.PRNGKey(1), 0.5)
    theta = FT.T_map(state.s_hat, fcfg)
    grads = [jax.grad(model.loss_fn)(theta, b) for b in (b1, b2)]
    want = np.concatenate([np.ravel(x) for x in REF.probe(
        jax.tree.map(lambda a, b: (a + b) / 2, *grads))])
    got = np.asarray(m["grad_probe"])
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)
    # every leaf is read, about PROBE_SIZE coordinates of a large one
    sizes = [np.size(x) for x in REF.probe(grads[0])]
    assert len(sizes) == len(jax.tree.leaves(grads[0])) and min(sizes) >= 1
    assert max(sizes) <= FT.PROBE_SIZE


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_oracle_output_is_rounded_once_by_an_explicit_op(dtype):
    """S_i = theta - rho g is computed in float32 and rounded once to the
    parameter dtype, by an op that XLA keeps even where it is allowed
    excess precision (a bare convert feeding the drift may be dropped)."""
    fcfg = FT.FedLMConfig(n_clients=1, rho=0.05)
    model = _model(TINY, dtype)
    params = _params(model, 7)
    batch = _batch(jax.random.PRNGKey(3), TINY["vocab_size"])
    problem = FT.make_problem(model, fcfg)
    s_i, _ = problem.s_bar_metrics(batch, params)
    g = jax.grad(model.loss_fn)(params, batch)
    for a, th, gg in zip(jax.tree.leaves(s_i), jax.tree.leaves(params),
                         jax.tree.leaves(g)):
        want = (th.astype(jnp.float32) - 0.05 * gg.astype(jnp.float32)
                ).astype(th.dtype)
        assert a.dtype == th.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(want, np.float32))
    hlo = jax.jit(problem.s_bar).lower(batch, params).as_text()
    assert ("reduce_precision" in hlo) == (dtype == "bfloat16")
