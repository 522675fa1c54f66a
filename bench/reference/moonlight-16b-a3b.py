"""Plain float32 reference of federated rounds of Moonlight-16B-A3B at the
cut its configuration states, written from the configuration alone.

The model (model_type deepseek_v3; DeepSeek-V2's latent attention,
arXiv:2405.04434, and DeepSeek-V3's routing, arXiv:2412.19437, with the
departures the configuration file lists): token embeddings; pre-RMSNorm
blocks (eps ``rms_norm_eps``); latent attention in every block, with
queries projected directly to ``qk_nope_head_dim + qk_rope_head_dim`` per
head, keys and values through a ``kv_lora_rank`` latent (RMSNorm on the
latent) and one rotary key of ``qk_rope_head_dim`` shared by the heads,
rotary embeddings on interleaved pairs, causal softmax at scale
1/sqrt(qk_nope_head_dim + qk_rope_head_dim), values of ``v_head_dim``;
``first_k_dense_replace`` leading blocks with a SwiGLU MLP of width
``intermediate_size``; the other blocks with a mixture of experts: sigmoid
scores over all ``n_routed_experts x expert_parallel`` experts, the top
``num_experts_per_tok`` of them, their scores normalised to sum 1 and
scaled by ``routed_scaling_factor``, plus the shared experts (one SwiGLU of
width ``n_shared_experts x moe_intermediate_size``) on every token. Only
the ``n_routed_experts`` experts held here (the first of them) add their
part; what the absent experts would add is left out, as the configuration
states. Then a final RMSNorm, an untied head over the vocabulary (padded
to a multiple of 128, the padding masked) and the mean token
cross-entropy.

Each held expert is computed densely on every token and weighted by its
gate (zero where it was not chosen); attention and the head are computed
in chunks of query rows so that they fit.

The federated round is the one ``bench/reference/whisper-base.py``
writes (the same wire), with the oracle outputs rounded to the parameter
dtype as soon as each client's gradient is complete, so that only one
client's float32 gradient is live at a time; the variates wait on the
host while the oracles run. Every product is computed in float32 at
``highest`` precision (or one step lower for a control, see
``bench/precision.py``). The first round also keeps the all-client mean of
the float32 gradients at the coordinates of ``probe``.
"""
from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np


def _load_whisper():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "whisper-base.py")
    spec = importlib.util.spec_from_file_location("bench_reference_wire",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_W = _load_whisper()
wire, leaf_names, NEG = _W.wire, _W.leaf_names, _W.NEG
Q_CHUNK = 512           # query rows per chunk of attention and of the head
PROBE = 4096            # coordinates of each leaf in the gradient probe


def probe(tree):
    """Each leaf at a fixed grid of its coordinates, flattened: along every
    axis of length d, every max(1, d // m)-th index, m the ndim-th root of
    ``PROBE`` rounded (the program's ``grad_probe`` reads the same
    coordinates)."""
    out = []
    for x in jax.tree.leaves(tree):
        m = max(1, round(PROBE ** (1.0 / x.ndim))) if x.ndim else 1
        for ax, d in enumerate(x.shape):
            x = jax.lax.slice_in_dim(x, 0, d, max(1, d // m), axis=ax)
        out.append(x.reshape(-1))
    return out


class Moonlight:
    """Forward pass and loss of the configuration, in float32."""

    def __init__(self, cfg: dict, mm):
        self.mm = mm
        self.H = cfg["num_attention_heads"]
        self.r = cfg["kv_lora_rank"]
        self.dn, self.dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        self.dv = cfg["v_head_dim"]
        self.vocab = cfg["vocab_size"]
        self.theta = float(cfg["rope_theta"])
        self.eps = float(cfg["rms_norm_eps"])
        self.k = cfg["num_experts_per_tok"]
        self.held = cfg["n_routed_experts"]
        self.scale = float(cfg["routed_scaling_factor"])

    def norm(self, x, p):
        return _W._rmsnorm(x, p["scale"], self.eps)

    def _proj(self, x, w):
        *lead, d = x.shape
        return self.mm(x.reshape(-1, d), w).reshape(*lead, w.shape[-1])

    def attention(self, p, x):
        mm, H, dn, dr, dv = self.mm, self.H, self.dn, self.dr, self.dv
        u, S, _ = x.shape
        q = self._proj(x, p["wq"]).reshape(u, S, H, dn + dr)
        q = jnp.concatenate([q[..., :dn], _W._rope(q[..., dn:], self.theta)],
                            axis=-1)
        ckv = self._proj(x, p["wkv_a"])
        c = _W._rmsnorm(ckv[..., :self.r], p["kv_norm"]["scale"], self.eps)
        k_rope = _W._rope(ckv[..., None, self.r:], self.theta)
        kv = self._proj(c, p["wkv_b"]).reshape(u, S, H, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (u, S, H, dr))], axis=-1)
        v = kv[..., dn:]
        kT = k.transpose(0, 2, 3, 1)                        # (u, H, dk, S)
        vh = v.transpose(0, 2, 1, 3)                        # (u, H, S, dv)
        C = math.gcd(Q_CHUNK, S)
        nc = S // C

        @jax.checkpoint
        def chunk(i):
            qc = jax.lax.dynamic_slice_in_dim(q, i * C, C, 1)
            s = mm(qc.transpose(0, 2, 1, 3), kT) / math.sqrt(dn + dr)
            rows = i * C + jnp.arange(C)
            s = jnp.where(jnp.arange(S)[None, :] <= rows[:, None], s, NEG)
            return mm(jax.nn.softmax(s, axis=-1), vh)        # (u, H, C, dv)

        o = jax.lax.map(chunk, jnp.arange(nc))               # (nc, u, H, C, dv)
        o = o.transpose(1, 0, 3, 2, 4).reshape(u, S, H * dv)
        return self._proj(o, p["wo"])

    def mlp(self, p, x):
        h = jax.nn.silu(self._proj(x, p["w_gate"])) * self._proj(x, p["w_in"])
        return self._proj(h, p["w_out"])

    def gates(self, router, x):
        """(T, held) gate of each held expert on each token: its normalised,
        scaled score where it is among the token's top k, else 0."""
        scores = jax.nn.sigmoid(self.mm(x, router))          # (T, routed)
        top, idx = jax.lax.top_k(scores, self.k)
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
        chosen = jnp.zeros_like(scores).at[
            jnp.arange(x.shape[0])[:, None], idx].set(top * self.scale)
        return chosen[:, :self.held]

    def moe(self, p, x):
        u, S, d = x.shape
        xf = x.reshape(u * S, d)
        g = self.gates(p["router"], xf)

        @jax.checkpoint
        def expert(y, we):
            w, ge = we
            return y + ge[:, None] * self.mlp(w, xf), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(xf),
                            (p["experts"], g.T))
        if "shared" in p:
            y = y + self.mlp(p["shared"], xf)
        return y.reshape(u, S, d)

    def layer(self, x, p):
        x = x + self.attention(p["attn"], self.norm(x, p["norm1"]))
        h = self.norm(x, p["norm2"])
        return x + (self.moe(p["moe"], h) if "moe" in p
                    else self.mlp(p["mlp"], h))

    def token_ce_sum(self, params, tokens, labels):
        """Sum over the block's tokens of the cross-entropy."""
        x = jnp.take(params["embedding"]["embed"], tokens, axis=0)
        for stack in ("lead", "stack"):
            if stack in params:
                x, _ = jax.lax.scan(
                    jax.checkpoint(lambda x, p: (self.layer(x, p), None)),
                    x, params[stack][0])
        x = self.norm(x, params["final_norm"])
        head = params["embedding"]["lm_head"]
        u, S, d = x.shape
        C = math.gcd(Q_CHUNK, S)
        valid = jnp.arange(head.shape[0]) < self.vocab

        @jax.checkpoint
        def chunk(xy):
            xc, yc = xy
            logits = jnp.where(valid[None], self.mm(xc, head.T), NEG)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
            return jnp.sum(lse - tgt)

        xs = x.reshape(u * S // C, C, d)
        ys = labels.reshape(u * S // C, C)
        return jnp.sum(jax.lax.map(chunk, (xs, ys)))


class Reference:
    """``capture(params0, batch_at, n_steps)`` runs ``n_steps`` federated
    rounds from ``params0`` on the rounds ``batch_at(r) -> (batch, key,
    gamma)`` and returns each round's all-client mean loss, the server
    variate after one round (``v1``, alpha/p times the first aggregate, on
    the host), the per-leaf norms of the first aggregate and of the mirror
    parameter's change after all rounds, and the first round's all-client
    mean gradient ``probe`` (one array a leaf, on the host).
    ``half_batch`` plants the fault of a client oracle that leaves out
    half of each client's batch."""

    def __init__(self, config: dict, workload: dict, mm,
                 half_batch: bool = False):
        f = config["fedmm"]
        self.rho, self.wd = f["rho"], f["weight_decay"]
        self.alpha, self.bits, self.block = f["alpha"], f["quant_bits"], \
            f["quant_block"]
        self.n = workload["n_clients"]
        self.p = workload["participation"]
        self.dtype = jnp.dtype(config["dtype"])
        self.model = Moonlight(config, mm)
        self.ublock = workload.get("reference_block", 1)
        self.half = half_batch
        self._oracle = jax.jit(self._client_oracle)
        self._round = jax.jit(self._server, donate_argnums=(0, 1, 2))

    def _client_oracle(self, theta, batch):
        """Mean token loss, the oracle output theta - rho grad, rounded to
        the parameter dtype, and the gradient's ``probe``, for one client's
        batch; the gradient is summed in float32 over blocks of
        sequences."""
        b = batch["tokens"].shape[0]
        if self.half:
            batch = jax.tree.map(lambda x: x[:b // 2], batch)
            b //= 2
        u = min(self.ublock, b)
        nb = b // u
        blocks = jax.tree.map(lambda x: x.reshape((nb, u) + x.shape[1:]),
                              batch)
        f32 = jnp.float32
        zero = jax.tree.map(lambda t: jnp.zeros(t.shape, f32), theta)

        def loss(delta, bb):
            # differentiated at theta in float32 without a float32 copy of
            # theta: theta + 0 upcasts leaf by leaf where it is used
            th = jax.tree.map(lambda t, dd: t.astype(f32) + dd, theta, delta)
            return self.model.token_ce_sum(th, bb["tokens"], bb["labels"])

        def blk(carry, bb):
            tot, g = carry
            val, gg = jax.value_and_grad(loss)(zero, bb)
            return (tot + val, jax.tree.map(jnp.add, g, gg)), None

        (tot, g), _ = jax.lax.scan(blk, (f32(0.0), zero), blocks)
        ntok = b * batch["tokens"].shape[1]
        s_i = jax.tree.map(
            lambda t, gg: (t.astype(f32) - self.rho * gg / ntok).astype(
                self.dtype), theta, g)
        return tot / ntok, s_i, probe(jax.tree.map(lambda x: x / ntok, g))

    def _server(self, s_hat, v, v_i, s_i, key, gamma):
        """Everything of the round after the oracles: drifts, wire,
        aggregate, server step and variates. ``s_i`` is stacked over
        clients."""
        dt, f32 = self.dtype, jnp.float32
        k_part, k_quant = jax.random.split(key)
        active = jax.random.bernoulli(k_part, self.p, (self.n,))
        mask = active.astype(f32)
        qkeys = jax.random.split(k_quant, self.n)
        mu = 1.0 / self.n
        leaves, tdef = jax.tree.flatten(s_hat)
        sl = jax.tree.leaves(s_i)
        vl, vil = jax.tree.leaves(v), jax.tree.leaves(v_i)
        c1 = self.alpha / self.p
        s_new, v_new, vi_new = [], [], []
        for j, (s, si, vv, vi) in enumerate(zip(leaves, sl, vl, vil)):
            q_c = []
            for c in range(self.n):
                d = (si[c].astype(f32) - s.astype(f32)
                     - vi[c].astype(f32)).astype(dt)
                lk = jax.random.split(qkeys[c], len(leaves))[j]
                q = wire(lk, d.astype(f32), self.bits, self.block).astype(dt)
                q_c.append(q.astype(f32) * mask[c])
            a = sum(mu * q for q in q_c).astype(dt)
            h = (vv.astype(f32) + a.astype(f32) / self.p).astype(dt)
            s_new.append((s.astype(f32) + gamma * h.astype(f32)).astype(dt))
            v_new.append((vv.astype(f32) + c1 * a.astype(f32)).astype(dt))
            vi_new.append(jnp.stack([(vi[c].astype(f32) + c1 * q_c[c])
                                     .astype(dt) for c in range(self.n)]))
        return (jax.tree.unflatten(tdef, s_new),
                jax.tree.unflatten(tdef, v_new),
                jax.tree.unflatten(tdef, vi_new))

    def capture(self, params0, batch_at, n_steps: int) -> dict:
        dt, f32 = self.dtype, jnp.float32
        c = 1.0 / (1.0 + self.rho * self.wd)
        names = leaf_names(params0)
        norms = jax.jit(lambda t: jnp.stack(
            [jnp.sqrt(jnp.sum(jnp.square(x.astype(f32))))
             for x in jax.tree.leaves(t)]))
        view = jax.jit(lambda s: jax.tree.map(
            lambda x: (c * x.astype(f32)).astype(dt), s))
        p0 = jax.device_get(params0)            # the start, on the host
        s_hat = jax.tree.map(jnp.array, p0)
        v = jax.tree.map(np.zeros_like, p0)
        v_i = jax.tree.map(lambda x: np.zeros((self.n,) + x.shape, x.dtype),
                           p0)
        losses, agg_norms = [], None
        for r in range(n_steps):
            batch, key, gamma = batch_at(r)
            theta = view(s_hat)
            ls, ss, ps = [], [], []
            for cl in range(self.n):
                loss, s_i, pr = self._oracle(
                    theta, jax.tree.map(lambda x: x[cl], batch))
                ls.append(float(loss))
                ss.append(s_i)
                ps.append(jax.device_get(pr))
            if r == 0:
                grad_probe = [np.mean(x, axis=0) for x in zip(*ps)]
            del theta
            s_i = jax.tree.map(lambda *x: jnp.stack(x), *ss)
            del ss
            s_hat, v, v_i = self._round(s_hat, jax.device_put(v),
                                        jax.device_put(v_i), s_i, key,
                                        jnp.float32(gamma))
            del s_i
            losses.append(float(np.mean(ls)))
            if r == 0:
                agg_norms = np.asarray(norms(v)) * (self.p / self.alpha)
            v, v_i = jax.device_get((v, v_i))
            if r == 0:
                v1 = v
        change = np.asarray(norms(jax.tree.map(
            lambda a, b: a.astype(f32) - jnp.asarray(b).astype(f32),
            s_hat, p0)))
        return {"loss": losses, "v1": v1, "probe": grad_probe,
                "agg_norms": dict(zip(names, map(float, agg_norms))),
                "change_norms": dict(zip(names, map(float, change)))}
