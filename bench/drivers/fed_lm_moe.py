"""Federated rounds of a sparse-expert LM that holds one chip's share of its
experts (DeepSeek-V3-style configuration files, as published on the Hugging
Face hub), through ``repro.fed.trainer.make_train_step`` as
``bench/drivers/fed_lm.py`` drives them, with these differences:

* the configuration's keys are the published ones; ``deployment`` says over
  how many chips each expert layer is divided (``expert_parallel``): the
  router routes over ``n_routed_experts x expert_parallel`` experts, of
  which the ``n_routed_experts`` held here compute their part;
* the batches are tokens alone, drawn from the vocabulary slice;
* the state is donated to each round, so that one copy of it is live;
* the end-to-end metric is ``rounds_per_s``; a round whose loss is not
  finite, or in which a participating client's oracle output held a NaN
  or an inf (``n_nonfinite`` of ``repro.api.step``), counts as failed;
* the window keeps each round's assignments per held expert per MoE layer,
  which ``train_mfu.moonlight`` and ``expert_load_imbalance.moonlight``
  read;
* ``correct`` also reads the first round's gradient probe and server
  variates element by element (``readings``).

Workload keys as ``fed_lm.py`` (no ``frames_scale``), and
``reference_block`` (sequences per block of the reference's gradient).
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic
from bench.drivers import fed_lm
# a program without an expert layer that holds a share of the experts
# cannot run these cells: fail as the driver loads, before any run
from repro.models.moe import moe_share_block  # noqa: F401


def arch_config(config: dict):
    """The repo's architecture config at the sizes the configuration file
    states; a setting the program does not implement is an error."""
    import repro.configs as RC
    needs = {"q_lora_rank": None, "n_group": 1, "topk_group": 1,
             "topk_method": "noaux_tc", "norm_topk_prob": True,
             "moe_layer_freq": 1, "hidden_act": "silu",
             "tie_word_embeddings": False, "attention_bias": False,
             "num_nextn_predict_layers": 0, "scoring_func": "sigmoid"}
    for k, want in needs.items():
        if config.get(k, want) != want:
            raise ValueError(f"{config['name']}: {k}={config[k]!r} is not "
                             f"implemented (only {want!r})")
    held = config["n_routed_experts"]
    return dataclasses.replace(
        RC.get(config["arch"]),
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        n_experts=held * config["deployment"]["expert_parallel"],
        experts_held=held, expert_base=0,
        top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        first_dense=config["first_k_dense_replace"],
        routed_scale=float(config["routed_scaling_factor"]),
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), dtype=config["dtype"])


class Cell(fed_lm.Cell):
    def _inputs(self):
        wl, cfg = self.wl, self.arch
        key = traffic.seed_key(self.seed)
        self.k_weights, k_data = jax.random.split(key)
        cdf = jax.jit(traffic.client_token_cdf, static_argnums=(0, 1, 2))(
            wl["n_clients"], cfg.vocab, wl["token_skew"])

        @jax.jit
        def feed(r):
            k = jax.random.fold_in(k_data, r)
            kb, ks = jax.random.split(k)
            batch = traffic.lm_round_batch(
                kb, cdf, wl["n_clients"], wl["local_batch"], wl["seq_len"],
                0, cfg.d_model, 0.0, jnp.bfloat16)
            return {"tokens": batch["tokens"], "labels": batch["labels"]}, ks

        self.feed = feed
        self.gamma = lambda r: np.float32(wl["gamma"] / np.sqrt(1.0 + r))

    def prepare(self):
        from repro.models.model import build_model
        self.arch = arch_config(self.config)
        self.model = build_model(self.arch)
        self.names = [jax.tree_util.keystr(p) for p, _ in
                      jax.tree_util.tree_leaves_with_path(jax.eval_shape(
                          self.model.init, jax.random.PRNGKey(0)))]
        self._inputs()

    def setup(self):
        from repro.fed import trainer as FT

        wl, config = self.wl, self.config
        self.prepare()
        f = config["fedmm"]
        fcfg = FT.FedLMConfig(
            n_clients=wl["n_clients"], rho=f["rho"],
            weight_decay=f["weight_decay"], p=wl["participation"],
            alpha=f["alpha"], quant_bits=f["quant_bits"],
            quant_block=f["quant_block"], quant_dither=f["quant_dither"],
            use_cv=f["use_cv"], client_mode=wl["client_mode"])
        self.step = jax.jit(FT.make_train_step(self.model, fcfg),
                            donate_argnums=0)
        shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        n = wl["n_clients"]
        state = FT.FedLMState(
            s_hat=fed_lm.init_params(shapes, self.k_weights),
            v=jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes),
            v_i=jax.tree.map(lambda s: jnp.zeros((n,) + s.shape, s.dtype),
                             shapes),
            step=jnp.asarray(0))
        # the first rounds, through the window's own step and feed: the
        # reference follows exactly these
        losses = []
        for r in range(wl["check_steps"]):
            batch, k, g = self.batch_at(r)
            state, m = self.step(state, batch, k, g)
            loss, bad = jax.device_get((m["loss"], m["n_nonfinite"]))
            if bad or not np.isfinite(loss):
                raise RuntimeError(
                    f"round {r}: loss {loss}, {bad} client(s) with a "
                    f"non-finite oracle output")
            losses.append(float(loss))
            if r == 0:
                agg = np.asarray(jax.jit(fed_lm._tree_norms)(state.v)) \
                    * (wl["participation"] / f["alpha"])
                v1 = jax.device_get(state.v)
                probe = np.asarray(m["grad_probe"])
        params = fed_lm.init_params(shapes, self.k_weights)
        change = np.asarray(jax.jit(lambda a, b: fed_lm._tree_norms(
            jax.tree.map(lambda x, y: x.astype(jnp.float32)
                         - y.astype(jnp.float32), a, b)))(state.s_hat,
                                                          params))
        del params
        self.capture = {"loss": losses, "v1": v1, "probe": probe,
                        "agg_norms": dict(zip(self.names, map(float, agg))),
                        "change_norms": dict(zip(self.names,
                                                 map(float, change)))}
        self.state, self.r = state, wl["check_steps"]
        self.tokens_per_client = wl["local_batch"] * wl["seq_len"]

    def window(self, seconds: float) -> dict:
        spans = self.ctx.spans
        state, r = self.state, self.r
        rounds = tokens = failed = 0
        loads = []
        t0 = time.perf_counter()
        while True:
            with spans("batch"):
                batch, k = self.feed(r)
            with spans("dispatch"):
                state, m = self.step(state, batch, k, self.gamma(r))
            with spans("loss_read"):
                loss, n_active, bad, load = jax.device_get(
                    (m["loss"], m["n_active"], m["n_nonfinite"],
                     m["expert_load"]))
            now = time.perf_counter()
            rounds += 1
            r += 1
            if bad or not np.isfinite(loss):
                failed += 1
            tokens += int(round(float(n_active))) * self.tokens_per_client
            loads.append(np.asarray(load))
            if now - t0 >= seconds:
                break
        elapsed = now - t0
        self.state, self.r = state, r
        return {"elapsed": elapsed, "attempted": rounds, "failed": failed,
                "units": rounds, "tokens": tokens,
                "expert_load": np.stack(loads),
                "e2e": {"rounds_per_s": rounds / elapsed}}

    def readings(self, capture: dict, ref: dict) -> dict:
        """``fed_lm``'s ``loss_gap``, ``grad_gap`` and ``change_gap``, and
        two numbers element by element:

        * ``oracle_gap``: the worst leaf's relative distance between the
          first round's all-client mean gradient at the probe's coordinates
          (``repro.fed.trainer.grad_probe``) and the reference's float32
          one (a program without the probe reads 1). With a bf16 state the
          oracle output keeps nothing of rho * g below half a step of
          theta, so the numbers after it hardly see the products; this one
          reads the gradient before that rounding;
        * ``agg_gap``: the relative norm of the difference of the first
          round's server variates (alpha/p times the first aggregate) over
          every leaf (a state without one reads 1)."""
        out = super().readings(capture, ref)
        out["oracle_gap"], leaf = probe_gap(capture.get("probe"),
                                            ref["probe"], self.names)
        out["agg_gap"] = tree_rel_gap(capture.get("v1"), ref["v1"])
        self.notes.append(f"oracle_gap {out['oracle_gap']} worst leaf "
                          f"{leaf}")
        return out


def probe_gap(prog, ref: list, names: list) -> tuple:
    """The worst leaf's ||prog - ref|| / ||ref|| of two gradient probes:
    the program's flat vector (or per-leaf arrays, as the reference gives
    them), cut at the lengths of the reference's per-leaf arrays. Returns
    ``(gap, leaf)``."""
    if prog is None:
        return 1.0, "no probe"
    prog = np.concatenate([np.ravel(x) for x in prog]) \
        if isinstance(prog, list) else np.ravel(prog)
    prog = prog.astype(np.float64)
    if prog.size != sum(np.size(r) for r in ref):
        return math.inf, "probe size differs"
    worst, where = 0.0, ""
    for name, p, r in zip(names, np.split(prog, np.cumsum(
            [np.size(r) for r in ref])[:-1]), ref):
        r = np.asarray(r, np.float64).reshape(-1)
        g = float(np.linalg.norm(p - r) / max(np.linalg.norm(r), 1e-300))
        if not math.isfinite(g):
            return math.inf, name
        if g > worst:
            worst, where = g, name
    return worst, where


def tree_rel_gap(a, b) -> float:
    """||a - b|| / ||b|| over all leaves of two host trees, in float64; a
    missing or non-finite ``a`` reads 1 or infinitely far."""
    if a is None:
        return 1.0
    num = den = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        y = np.asarray(y, np.float32)
        d = np.asarray(x, np.float32) - y
        num += float(np.sum(np.square(d, dtype=np.float64)))
        den += float(np.sum(np.square(y, dtype=np.float64)))
    if not np.isfinite(num):
        return float("inf")
    return float(np.sqrt(num / max(den, 1e-300)))
