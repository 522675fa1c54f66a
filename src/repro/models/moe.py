"""Mixture-of-Experts layer: top-k router + capacity dropping with
*grouped one-hot einsum dispatch* (MaxText/Flaxformer style).

Tokens are processed in groups of <=256: within a group, position-in-expert
comes from a cumulative sum over the (token, choice) one-hot mask, and
dispatch/combine are einsums — every op propagates sharding under GSPMD
(group dim follows the batch axes, expert dim is sharded over 'model' =
expert parallelism; the dispatch einsum lowers to the expected all-to-all
pattern). A sort/scatter implementation is shorter but forces full
rematerialization under SPMD partitioning (observed TB-scale buffers), so
einsum dispatch is the production choice despite its O(g * E*C * d) flops
overhead — group size 256 keeps that under ~15% of expert compute for the
worst assigned config (qwen3: top-8 of 128).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import dense_init, mlp, mlp_init


def moe_init(key, cfg, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.d_ff

    def stack(k, din, dout):
        return jax.vmap(lambda kk: dense_init(kk, din, dout, dtype))(
            jax.random.split(k, E))

    return {
        "router": dense_init(k1, d, E, jnp.float32),
        "experts": {
            "w_gate": stack(k2, d, ff),
            "w_in": stack(k3, d, ff),
            "w_out": stack(k4, ff, d),
        },
    }


def _group_tokens(x, group: int):
    """(B, S, d) -> (G, g, d) with the sharded batch dim outermost."""
    B, S, d = x.shape
    g = group
    while S % g:
        g //= 2
    return x.reshape(B * (S // g), g, d), g


def moe_block(params, cfg, x, group: int = 0):
    """x: (B, S, d) -> (B, S, d), aux load-balance loss (scalar)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    group = group or getattr(cfg, "moe_group", 256) or 256
    xg, g = _group_tokens(x, min(group, S))
    G = xg.shape[0]

    logits = (xg.astype(jnp.float32) @ params["router"])          # (G, g, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, k)                           # (G, g, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch/Mixtral convention)
    me = jnp.mean(probs, axis=(0, 1))                             # (E,)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(eid, E, dtype=jnp.float32),
                          axis=2), axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    C = max(1, int(g * k / E * cfg.capacity_factor))

    mask = jax.nn.one_hot(eid, E, dtype=jnp.float32)              # (G, g, k, E)
    # position-in-expert: cumulative count over (token, choice) order
    mflat = mask.reshape(G, g * k, E)
    pos_f = jnp.cumsum(mflat, axis=1) - mflat                     # rank if kept
    pos = jnp.sum(pos_f * mflat, axis=-1).reshape(G, g, k)        # (G, g, k)
    keep = (pos < C).astype(jnp.float32)
    slot = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep[..., None]

    dispatch = jnp.einsum("Ntke,Ntkc->Ntec", mask, slot)          # (G, g, E, C)
    combine = jnp.einsum("Ntke,Ntkc->Ntec",
                         mask * gate[..., None], slot)            # gated
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)

    buf = jnp.einsum("Ntec,Ntd->Necd", dispatch, xg)              # (G, E, C, d)

    w = params["experts"]
    h = jax.nn.silu(jnp.einsum("Necd,edf->Necf", buf, w["w_gate"])) \
        * jnp.einsum("Necd,edf->Necf", buf, w["w_in"])
    out_buf = jnp.einsum("Necf,efd->Necd", h, w["w_out"])         # (G, E, C, d)

    y = jnp.einsum("Ntec,Necd->Ntd", combine, out_buf)            # (G, g, d)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# one chip's share of the experts, routed over all of them, without drops
# (DeepSeek-V3 routing, arXiv:2412.19437 §2.1.2)
# ---------------------------------------------------------------------------

def moe_share_init(key, cfg, dtype):
    """The router over all ``n_experts``, the ``experts_held`` experts of
    this share, and the shared experts as one SwiGLU of their summed
    width."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    E, d = cfg.experts_held or cfg.n_experts, cfg.d_model
    de = cfg.d_expert or cfg.d_ff

    def stack(k, din, dout):
        return jax.vmap(lambda kk: dense_init(kk, din, dout, dtype))(
            jax.random.split(k, E))

    p = {"router": dense_init(k1, d, cfg.n_experts, dtype),
         "experts": {"w_gate": stack(k2, d, de), "w_in": stack(k3, d, de),
                     "w_out": stack(k4, de, d)}}
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(k5, d, cfg.n_shared_experts * de, dtype)
    return p


def route(router, cfg, x):
    """x: (T, d) -> (expert ids (T, k), gates (T, k) f32). Sigmoid scores
    in f32 pick the top k (the selection bias of ``noaux_tc`` is held at
    0); the chosen scores are normalised to sum 1 and scaled by
    ``routed_scale``."""
    scores = jax.nn.sigmoid(x.astype(jnp.float32)
                            @ router.astype(jnp.float32))
    gate, eid = jax.lax.top_k(scores, cfg.top_k)
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-20)
    return eid, gate * cfg.routed_scale


class _Plan(NamedTuple):
    """Where each held assignment lies: the (token, choice) assignments
    sorted by held expert (``order``), each expert's ``counts`` and
    ``starts`` in that order, and for each of the worst case's tiles its
    ``expert`` and first ``row`` in the expert's run; tiles from ``live``
    on are empty."""
    order: jnp.ndarray
    counts: jnp.ndarray
    starts: jnp.ndarray
    expert: jnp.ndarray
    row: jnp.ndarray
    live: jnp.ndarray


def _tile_plan(eid, base, held, tile):
    """Sort the (token, choice) assignments by held expert and lay each
    expert's run out in whole tiles of ``tile`` rows."""
    k = eid.shape[-1]
    local = eid.reshape(-1) - base
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    starts = jnp.cumsum(counts) - counts
    ntile = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(ntile)
    # worst case: every token sends min(k, held) choices here
    n_max = -(-eid.shape[0] * min(k, held) // tile) + held
    t = jnp.arange(n_max)
    e_t = jnp.minimum(jnp.searchsorted(tile_end, t, side="right"), held - 1)
    r0_t = (t - (tile_end[e_t] - ntile[e_t])) * tile
    return _Plan(order, counts, starts, e_t.astype(jnp.int32),
                 r0_t.astype(jnp.int32), tile_end[-1])


def _tile_rows(plan, t, tile, k):
    """Tile ``t``'s expert, assignments, tokens and which rows are real."""
    e = plan.expert[t]
    j = plan.row[t] + jnp.arange(tile)
    valid = j < plan.counts[e]
    a = plan.order[jnp.minimum(plan.starts[e] + j, plan.order.shape[0] - 1)]
    return e, a, a // k, valid


def _expert_fwd(w, e, xt):
    wg, wi, wo = (jax.lax.dynamic_index_in_dim(w[n], e, keepdims=False)
                  for n in ("w_gate", "w_in", "w_out"))
    a = jnp.dot(xt, wg, preferred_element_type=jnp.float32)
    b = jnp.dot(xt, wi, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(a) * b).astype(xt.dtype)
    return a, b, h, jnp.dot(h, wo, preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _held_experts(w, x, gates, plan, tile, k):
    return _held_experts_fwd(w, x, gates, plan, tile, k)[0]


def _held_experts_fwd(w, x, gates, plan, tile, k):
    """y (T, d) f32: each live tile's rows through its expert, gated and
    added back at their tokens; dead tiles are skipped."""
    g_flat = gates.reshape(-1)

    def body(y, t):
        def live(y):
            e, a, tok, valid = _tile_rows(plan, t, tile, k)
            out = _expert_fwd(w, e, x[tok])[3]
            g = jnp.where(valid, g_flat[a], 0.0)
            return y.at[tok].add(out * g[:, None])
        return jax.lax.cond(t < plan.live, live, lambda y: y, y), None

    y0 = jnp.zeros(x.shape, jnp.float32)
    y, _ = jax.lax.scan(body, y0, jnp.arange(plan.expert.shape[0]))
    return y, (w, x, gates, plan)


def _held_experts_bwd(tile, k, res, dy):
    """Recomputes each live tile's expert; accumulates the held experts'
    weight gradients slice by slice, the token and gate gradients by
    scatter-add."""
    w, x, gates, plan = res
    g_flat = gates.reshape(-1)
    xd = x.dtype

    def body(carry, t):
        def live(carry):
            dw, dx, dg = carry
            e, a, tok, valid = _tile_rows(plan, t, tile, k)
            xt = x[tok]
            a_pre, b_pre, h, out = _expert_fwd(w, e, xt)
            g = jnp.where(valid, g_flat[a], 0.0)
            dyt = dy[tok]
            dg = dg.at[a].add(jnp.where(valid, jnp.sum(out * dyt, -1), 0.0))
            do = (dyt * g[:, None]).astype(xd)
            wg, wi, wo = (jax.lax.dynamic_index_in_dim(w[n], e,
                                                       keepdims=False)
                          for n in ("w_gate", "w_in", "w_out"))
            dh = jnp.dot(do, wo.T, preferred_element_type=jnp.float32)
            sig = jax.nn.sigmoid(a_pre)
            da = (dh * b_pre * sig * (1.0 + a_pre * (1.0 - sig))).astype(xd)
            db = (dh * a_pre * sig).astype(xd)
            dxt = (jnp.dot(da, wg.T, preferred_element_type=jnp.float32)
                   + jnp.dot(db, wi.T, preferred_element_type=jnp.float32))
            dx = dx.at[tok].add(dxt)

            def acc(name, grad):
                return dw[name].at[e].add(grad)
            dw = {"w_gate": acc("w_gate", jnp.dot(
                      xt.T, da, preferred_element_type=jnp.float32)),
                  "w_in": acc("w_in", jnp.dot(
                      xt.T, db, preferred_element_type=jnp.float32)),
                  "w_out": acc("w_out", jnp.dot(
                      h.T, do, preferred_element_type=jnp.float32))}
            return dw, dx, dg
        return jax.lax.cond(t < plan.live, live, lambda c: c, carry), None

    zero = (jax.tree.map(lambda v: jnp.zeros(v.shape, jnp.float32), w),
            jnp.zeros(x.shape, jnp.float32),
            jnp.zeros(g_flat.shape, jnp.float32))
    with jax.named_scope("model.moe.experts.bwd"):
        (dw, dx, dg), _ = jax.lax.scan(body, zero,
                                       jnp.arange(plan.expert.shape[0]))
    dplan = jax.tree.map(
        lambda p: np.zeros(p.shape, jax.dtypes.float0), plan)
    return (jax.tree.map(lambda d, v: d.astype(v.dtype), dw, w),
            dx.astype(x.dtype), dg.reshape(gates.shape).astype(gates.dtype),
            dplan)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def moe_share_block(params, cfg, x):
    """x: (B, S, d) -> (y (B, S, d), assignments per held expert (E_h,)).

    Routes every token over all ``n_experts`` and computes only the part
    of the result that the held experts [expert_base, expert_base +
    experts_held) give, for every assignment they receive (no capacity,
    nothing dropped): the assignments are sorted by expert and run in
    tiles of ``moe_group`` rows, each through one expert, so the work
    follows the assignments held here. The shared experts see every
    token. What the absent experts would add is left out."""
    B, S, d = x.shape
    held = cfg.experts_held or cfg.n_experts
    tile = cfg.moe_group
    xf = x.reshape(B * S, d)
    with jax.named_scope("model.moe.route"):
        eid, gates = route(params["router"], cfg, xf)
        plan = _tile_plan(eid, cfg.expert_base, held, tile)
    with jax.named_scope("model.moe.experts"):
        y = _held_experts(params["experts"], xf, gates, plan, tile,
                          cfg.top_k)
    if cfg.n_shared_experts:
        with jax.named_scope("model.moe.shared"):
            y = y + mlp(params["shared"], x).reshape(B * S, d).astype(
                jnp.float32)
    return y.astype(x.dtype).reshape(B, S, d), plan.counts
