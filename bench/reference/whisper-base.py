"""Plain float32 reference of federated rounds of whisper-base as the
repo implements it, written from the configuration alone.

The model (arXiv:2212.04356, with the repo's departures that the
configuration file lists): a bidirectional encoder over the stub frames and
a causal decoder with cross-attention, pre-RMSNorm blocks (eps 1e-5),
SwiGLU MLPs, rotary position embeddings (interleaved pairs) on encoder and
decoder self-attention and none on cross-attention, an untied output head
over the vocabulary padded to a multiple of 128 with the padding masked
out, and the mean token cross-entropy as the loss.

One federated round (the paper's Algorithm 2 with the quadratic surrogate
of Example 1): the view theta = S / (1 + rho wd) (the l2 prox), each
client's oracle S_i = theta - rho grad_i(theta), the drift
d_i = S_i - S - V_i, the 8-bit wire (groups along the last axis, max-abs
scale, stochastic rounding with the hash dither keyed from the round key),
the mu-weighted aggregate over the participating clients, the server step
S + gamma (V + agg / p), and the variate updates V += alpha/p agg,
V_i += alpha/p q_i.

Every product is computed in float32 at ``highest`` precision (or one step
lower for a control, see ``bench/precision.py``), in blocks of utterances
so that it fits. Every stored tensor of the round (the mirror parameter,
the view, each oracle output and drift, each decoded payload, the
aggregate and the variates) is rounded to the configuration's parameter
dtype, as the configuration states; all arithmetic between those points is
float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x (u, S, H, hd): rotate interleaved pairs by position."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


class Whisper:
    """Forward pass and loss of the configuration, in float32."""

    def __init__(self, cfg: dict, mm):
        self.mm = mm
        self.H, self.KV = cfg["n_heads"], cfg["n_kv_heads"]
        self.hd = cfg["head_dim"]
        self.vocab = cfg["vocab"]
        self.theta = float(cfg["rope_theta"])
        self.eps = float(cfg["norm_eps"])

    def norm(self, x, p):
        return _rmsnorm(x, p["scale"], self.eps)

    def _proj(self, x, w):
        u, s, d = x.shape
        return self.mm(x.reshape(u * s, d), w).reshape(u, s, w.shape[-1])

    def attention(self, p, xq, xkv, causal, rope):
        mm, H, KV, hd = self.mm, self.H, self.KV, self.hd
        u, Sq, _ = xq.shape
        Sk = xkv.shape[1]
        q = self._proj(xq, p["wq"]).reshape(u, Sq, H, hd)
        k = self._proj(xkv, p["wk"]).reshape(u, Sk, KV, hd)
        v = self._proj(xkv, p["wv"]).reshape(u, Sk, KV, hd)
        if rope:
            q, k = _rope(q, self.theta), _rope(k, self.theta)
        if KV != H:           # grouped heads: query head h reads kv head h // (H/KV)
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
        s = mm(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1)) \
            / math.sqrt(hd)                                  # (u, H, Sq, Sk)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((Sq, Sk), bool)), s, NEG)
        w = jax.nn.softmax(s, axis=-1)
        o = mm(w, v.transpose(0, 2, 1, 3))                    # (u, H, Sq, hd)
        o = o.transpose(0, 2, 1, 3).reshape(u, Sq, H * hd)
        return self._proj(o, p["wo"])

    def mlp(self, p, x):
        h = jax.nn.silu(self._proj(x, p["w_gate"])) * self._proj(x, p["w_in"])
        return self._proj(h, p["w_out"])

    def encode(self, params, frames):
        enc = params["encoder"][0]

        @jax.checkpoint
        def layer(x, p):
            h = self.norm(x, p["norm1"])
            x = x + self.attention(p["attn"], h, h, causal=False, rope=True)
            return x + self.mlp(p["mlp"], self.norm(x, p["norm2"])), None

        x, _ = jax.lax.scan(layer, frames, enc)
        return self.norm(x, params["enc_norm"])

    def token_ce_sum(self, params, tokens, labels, frames):
        """Sum over the block's tokens of the cross-entropy."""
        enc_out = self.encode(params, frames)
        dec = params["stack"][0]
        x = jnp.take(params["embedding"]["embed"], tokens, axis=0)

        @jax.checkpoint
        def layer(x, p):
            h = self.norm(x, p["norm1"])
            x = x + self.attention(p["attn"], h, h, causal=True, rope=True)
            x = x + self.attention(p["xattn"], self.norm(x, p["norm_x"]),
                                   enc_out, causal=False, rope=False)
            return x + self.mlp(p["mlp"], self.norm(x, p["norm2"])), None

        x, _ = jax.lax.scan(layer, x, dec)
        x = self.norm(x, params["final_norm"])
        head = params["embedding"]["lm_head"]
        u, S, d = x.shape
        logits = self.mm(x.reshape(u * S, d), head.T)
        valid = jnp.arange(head.shape[0]) < self.vocab
        logits = jnp.where(valid[None], logits, NEG)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, labels.reshape(-1, 1), axis=-1)[:, 0]
        return jnp.sum(lse - tgt)


# ---------------------------------------------------------------------------
# the wire: 8-bit groups along the last axis, hash dither
# ---------------------------------------------------------------------------

def wire_group(D: int, block: int) -> int:
    """Group width along the last axis: the largest power of two that
    divides the width each of up to 32 shards holds, at most ``block``."""
    per = D // 32 if D % 32 == 0 else (D // 16 if D % 16 == 0 else D)
    g = 1
    while per % (g * 2) == 0 and g * 2 <= block:
        g *= 2
    return g


def hash_uniform(key, shape):
    """Uniforms in [0, 1) at 24-bit resolution from a murmur3 finalizer
    of the row-major element index, seeded by the key's first and last
    words."""
    kd = jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
    seed = kd[0] ^ kd[-1]
    idx = jnp.arange(math.prod(shape), dtype=jnp.uint32).reshape(shape)
    x = idx * jnp.uint32(2654435761) + seed
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return (x >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def wire(key, x, bits: int, block: int):
    """Quantize and dequantize one f32 leaf as it crosses the wire."""
    if x.ndim == 0:
        return x
    D = x.shape[-1]
    g = wire_group(D, block)
    if g < 2:
        return x
    levels = 2.0 ** (bits - 1) - 1.0
    u = hash_uniform(key, x.shape).reshape(x.shape[:-1] + (D // g, g))
    xg = x.reshape(x.shape[:-1] + (D // g, g))
    scale = jnp.max(jnp.abs(xg), axis=-1, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    y = xg / safe * levels
    lo = jnp.floor(y)
    q = lo + (u < (y - lo)).astype(jnp.float32)
    out = jnp.where(scale > 0, q * safe * (1.0 / levels), 0.0)
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# federated rounds
# ---------------------------------------------------------------------------

def leaf_names(tree):
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_leaves_with_path(tree)]


class Reference:
    """``capture(params0, batch_at, n_steps)`` runs ``n_steps`` federated
    rounds from ``params0`` on the rounds ``batch_at(r) -> (batch, key,
    gamma)`` and returns what the timed path is compared on: each round's
    all-client mean loss, the per-leaf norms of the first aggregate (read
    off the server variate after one round) and of the mirror parameter's
    change after all rounds. ``half_batch`` plants the fault of a client
    oracle that leaves out half of each client's batch."""

    def __init__(self, config: dict, workload: dict, mm,
                 half_batch: bool = False):
        self.cfg, self.wl = config, workload
        f = config["fedmm"]
        self.rho, self.wd = f["rho"], f["weight_decay"]
        self.alpha, self.bits, self.block = f["alpha"], f["quant_bits"], \
            f["quant_block"]
        self.n = workload["n_clients"]
        self.p = workload["participation"]
        self.dtype = jnp.dtype(config["dtype"])
        self.model = Whisper(config, mm)
        self.ublock = workload.get("reference_block", 4)
        self.half = half_batch
        self._grad = jax.jit(self._client_grad)
        self._round = jax.jit(self._server)

    def _client_grad(self, theta, batch):
        """Mean token loss and its gradient for one client's batch, summed
        over blocks of utterances."""
        b = batch["tokens"].shape[0]
        if self.half:
            batch = jax.tree.map(lambda x: x[:b // 2], batch)
            b //= 2
        u = min(self.ublock, b)
        nb = b // u
        blocks = jax.tree.map(lambda x: x.reshape((nb, u) + x.shape[1:]),
                              batch)
        theta = jax.tree.map(lambda x: x.astype(jnp.float32), theta)

        def blk(carry, bb):
            tot, g = carry
            val, gg = jax.value_and_grad(self.model.token_ce_sum)(
                theta, bb["tokens"], bb["labels"],
                bb["frames"].astype(jnp.float32))
            return (tot + val, jax.tree.map(jnp.add, g, gg)), None

        zero = jax.tree.map(jnp.zeros_like, theta)
        (tot, g), _ = jax.lax.scan(blk, (jnp.float32(0.0), zero), blocks)
        ntok = b * batch["tokens"].shape[1]
        return tot / ntok, jax.tree.map(lambda x: x / ntok, g)

    def _server(self, s_hat, v, v_i, theta, grads, key, gamma):
        """Everything of the round after the oracles: drifts, wire,
        aggregate, server step and variates. ``grads`` is stacked over
        clients."""
        dt, f32 = self.dtype, jnp.float32
        k_part, k_quant = jax.random.split(key)
        active = jax.random.bernoulli(k_part, self.p, (self.n,))
        mask = active.astype(f32)
        qkeys = jax.random.split(k_quant, self.n)
        mu = 1.0 / self.n
        leaves, tdef = jax.tree.flatten(s_hat)
        th = jax.tree.leaves(theta)
        gl = jax.tree.leaves(grads)
        vl, vil = jax.tree.leaves(v), jax.tree.leaves(v_i)
        agg, qs = [], []
        for j, (s, t, g, vi) in enumerate(zip(leaves, th, gl, vil)):
            q_c = []
            for c in range(self.n):
                s_i = (t.astype(f32) - self.rho * g[c]).astype(dt)
                d = (s_i.astype(f32) - s.astype(f32)
                     - vi[c].astype(f32)).astype(dt)
                lk = jax.random.split(qkeys[c], len(leaves))[j]
                q = wire(lk, d.astype(f32), self.bits, self.block).astype(dt)
                q_c.append(q.astype(f32) * mask[c])
            qs.append(q_c)
            agg.append(sum(mu * q for q in q_c).astype(dt))
        c1 = self.alpha / self.p
        s_new, v_new, vi_new = [], [], []
        for s, a, vv, vi, q_c in zip(leaves, agg, vl, vil, qs):
            h = (vv.astype(f32) + a.astype(f32) / self.p).astype(dt)
            s_new.append((s.astype(f32) + gamma * h.astype(f32)).astype(dt))
            v_new.append((vv.astype(f32) + c1 * a.astype(f32)).astype(dt))
            vi_new.append(jnp.stack([(vi[c].astype(f32) + c1 * q_c[c])
                                     .astype(dt) for c in range(self.n)]))
        return (jax.tree.unflatten(tdef, s_new), jax.tree.unflatten(tdef, v_new),
                jax.tree.unflatten(tdef, vi_new))

    def capture(self, params0, batch_at, n_steps: int) -> dict:
        dt, f32 = self.dtype, jnp.float32
        c = 1.0 / (1.0 + self.rho * self.wd)
        names = leaf_names(params0)
        norms = jax.jit(lambda t: jnp.stack(
            [jnp.sqrt(jnp.sum(jnp.square(x.astype(f32))))
             for x in jax.tree.leaves(t)]))
        s_hat = params0
        v = jax.tree.map(jnp.zeros_like, params0)
        v_i = jax.tree.map(lambda x: jnp.zeros((self.n,) + x.shape, x.dtype),
                           params0)
        losses, agg_norms = [], None
        for r in range(n_steps):
            batch, key, gamma = batch_at(r)
            theta = jax.tree.map(lambda x: (c * x.astype(f32)).astype(dt),
                                 s_hat)
            ls, gs = [], []
            for cl in range(self.n):
                loss, g = self._grad(
                    theta, jax.tree.map(lambda x: x[cl], batch))
                ls.append(float(loss))
                gs.append(g)
            grads = jax.tree.map(lambda *x: jnp.stack(x), *gs)
            del gs
            s_hat, v, v_i = self._round(s_hat, v, v_i, theta, grads, key,
                                        jnp.float32(gamma))
            del grads
            losses.append(float(np.mean(ls)))
            if r == 0:
                agg_norms = np.asarray(norms(v)) * (self.p / self.alpha)
        change = np.asarray(norms(jax.tree.map(
            lambda a, b: a.astype(f32) - b.astype(f32), s_hat, params0)))
        return {"loss": losses,
                "agg_norms": dict(zip(names, map(float, agg_norms))),
                "change_norms": dict(zip(names, map(float, change)))}
