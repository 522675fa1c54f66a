#!/usr/bin/env python3
"""Smoke test: the system's main paths, run once on a TPU.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the paths that exist only across chips

One chip runs three phases, each through the entry point a user calls:

  (a) the paper path: federated dictionary learning at the MovieLens
      setting (p=500, K=50, 20 clients, participation 0.5, 8-bit wire)
      through ``api.run``, checked against the same rounds and keys run on
      the host CPU in this process;
  (b) the cohort scheduler: a population of 256 clients streamed in
      cohorts of 64 over a checksummed wire, crashed after a snapshot and
      resumed, checked bit-for-bit against the uninterrupted run;
  (c) the wire kernels at whisper-base leaf widths against their jnp
      oracles, then the federated LM trainer (``repro.launch.train``)
      training whisper-base at its published widths for a few steps, with
      the wire route of its compiled step checked against the one the
      compressor predicts.

``--four-chips`` runs only the mesh paths and what each is compared with:
the paper path on a 4-device client mesh (gather and reduce) and on a
2 edges x 2 clients mesh (two-tier) against one chip, and the LM trainer
with one silo per chip against logical clients on one chip.

Any failed check exits non-zero. Without a TPU, or outside a checkout, it
exits non-zero before printing any result. The last line of standard
output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import math
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(Exception):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each phase runs, at the chip's sizes."""
    paper_rounds: int = 30
    population: int = 256
    cohort: int = 64
    sched_rounds: int = 4
    kernel_rows: int = 51865         # whisper-base's (vocab, d_model) leaf
    kernel_cols: int = 512
    lm_preset: str = "full"
    lm_batch: int = 8
    lm_seq: int = 448                # Whisper's text context
    lm_clients: int = 4
    lm_steps: int = 5
    mesh_lm_steps: int = 2


# tolerances, stated once. The paper problem runs its matmuls at full f32
# precision: chip vs host CPU measured 1.8e-6, and 4.5e-4 with the TPU's
# default bf16 passes, which these limits reject.
PAPER_RTOL = 1e-4     # final objective, chip vs host CPU, same rounds/keys
MESH_RTOL = 1e-4      # objective per round, 4-chip mesh vs one chip
LM_MESH_RTOL = 2e-2   # bf16 losses, one silo per chip vs logical clients
# the history rows a mesh run must reproduce bit for bit: the A5 draws and
# the wire's byte accounting
ACCOUNTING = ("n_active", "uplink_bytes", "backbone_bytes", "comm_bytes",
              "omega_eff")


def _bit_equal(a, b) -> bool:
    import jax
    import numpy as np
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def _objective_rel(hist, ref) -> float:
    """The largest per-round relative gap between two objective rows."""
    import numpy as np
    return max(_rel(float(a), float(b)) for a, b in
               zip(np.asarray(hist["loss"]), np.asarray(ref["loss"])))


def _device_ids(tree) -> list:
    """The ids of the devices that hold a pytree's arrays."""
    import jax
    return sorted({d.id for leaf in jax.tree.leaves(tree)
                   for d in leaf.sharding.device_set})


def _route(compiled) -> str:
    """Which wire route a compiled program took."""
    return "kernel" if "tpu_custom_call" in compiled.as_text() else "jnp"


# ---------------------------------------------------------------------------
# (a) the paper path
# ---------------------------------------------------------------------------

def paper_inputs():
    """MovieLens-like data, the client split and the initial statistic,
    made once on the host CPU (so chip and reference start from the same
    bits); returned as host numpy."""
    import jax

    from benchmarks.fig1_dictlearn import make_setting
    from repro import api
    from repro.configs.dictlearn import MOVIELENS
    from repro.core.variational import make_dictlearn

    key = jax.random.PRNGKey(0)
    with jax.default_device(jax.devices("cpu")[0]):
        dl, clients, z = make_setting(MOVIELENS, key, reduced=False)
        theta0 = jax.random.normal(key, (dl.p, dl.K)) * 0.1
        s0 = api.as_problem(make_dictlearn(dl)).s_bar(z[:128], theta0)
        return dl, jax.device_get((clients, z, s0))


def paper_run(dl, host, device, sizes: Sizes, *, spec_kw=None, **run_kw):
    """``api.run`` of the MovieLens FedMM problem with ``device`` as the
    default device (a mesh, when given, spans its own devices)."""
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.configs.dictlearn import MOVIELENS as exp
    from repro.core import compression
    from repro.core.variational import make_dictlearn
    from repro.data.synthetic import client_minibatch_fn

    with jax.default_device(device):
        clients, z, s0 = jax.tree.map(jnp.asarray, host)
        fed = api.FederationSpec(
            n_clients=exp.n_clients, participation=exp.participation,
            alpha=exp.alpha,
            compressor=compression.block_quant(exp.quant_bits, 128),
            **(spec_kw or {}))
        st, hist = api.run(
            api.as_problem(make_dictlearn(dl)), s0,
            client_minibatch_fn(clients, exp.batch_size),
            lambda t: exp.beta_stepsize / jnp.sqrt(exp.beta_stepsize + t),
            spec=fed, key=jax.random.PRNGKey(0),
            n_rounds=sizes.paper_rounds, eval_batch=z[:512], **run_kw)
        return jax.block_until_ready((st, hist))


def phase_paper(sizes: Sizes, device):
    import jax
    import numpy as np

    dl, host = paper_inputs()
    t0 = time.time()
    st, hist = paper_run(dl, host, device, sizes)
    seconds = time.time() - t0
    _, ref = paper_run(dl, host, jax.devices("cpu")[0], sizes)
    loss, loss_ref = np.asarray(hist["loss"]), np.asarray(ref["loss"])
    check(np.isfinite(loss).all(), f"(a) objective not finite: {loss}")
    check(loss[-1] < loss[0],
          f"(a) objective did not fall: {loss[0]} -> {loss[-1]}")
    rel = _rel(float(loss[-1]), float(loss_ref[-1]))
    print(f"(a) paper path p={dl.p} K={dl.K}: objective {loss[0]:.6f} -> "
          f"{loss[-1]:.6f} over {len(loss)} rounds; host-CPU reference "
          f"{float(loss_ref[-1]):.6f}, rel diff {rel:.3e} "
          f"(limit {PAPER_RTOL:g})", flush=True)
    check(rel <= PAPER_RTOL, f"(a) final objective {float(loss[-1])} vs "
          f"CPU reference {float(loss_ref[-1])}: rel {rel} > {PAPER_RTOL}")
    print(f"(a) PASS  (setup: {seconds:.1f}s wall incl. compile)", flush=True)
    return dl, host


# ---------------------------------------------------------------------------
# (b) the cohort scheduler
# ---------------------------------------------------------------------------

def phase_scheduler(sizes: Sizes, dl, host, device):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.configs.dictlearn import MOVIELENS as exp
    from repro.core import compression
    from repro.core.variational import make_dictlearn
    from repro.faults import FaultSpec, ServerKilled
    from repro.sched import CohortScheduler

    _, z, s0_host = host
    per = z.shape[0] // sizes.population
    arena = np.asarray(z[:per * sizes.population]).reshape(
        sizes.population, per, z.shape[1])       # host-side client data

    def data_fn(t, k, ids):
        return jnp.asarray(arena[np.asarray(ids)])

    with jax.default_device(device):
        s0 = jax.tree.map(jnp.asarray, s0_host)
        problem = api.as_problem(make_dictlearn(dl))
        spec = api.FederationSpec(
            n_clients=sizes.population, participation=exp.participation,
            alpha=exp.alpha,
            compressor=compression.block_quant(8, 128, checksum=True),
            faults=FaultSpec())
        kw = dict(n_rounds=sizes.sched_rounds)
        t0 = time.time()
        st_ref, _, m_ref = CohortScheduler(
            problem, spec, cohort_size=sizes.cohort).run(
            s0, data_fn, 0.05, key=jax.random.PRNGKey(1), **kw)
        seconds = time.time() - t0
        kill = sizes.sched_rounds - 1
        killed = CohortScheduler(
            problem, dataclasses.replace(spec, faults=FaultSpec(
                kill_round=kill)), cohort_size=sizes.cohort)
        with tempfile.TemporaryDirectory() as ck:
            try:
                killed.run(s0, data_fn, 0.05, key=jax.random.PRNGKey(1),
                           checkpoint_dir=ck, **kw)
                crashed = False
            except ServerKilled:
                crashed = True
            check(crashed, f"(b) kill_round={kill} did not stop the server")
            snaps = sorted(glob.glob(os.path.join(ck, "round_*.snap")))
            check(snaps, "(b) no snapshot was written before the crash")
            st, _, m = killed.resume(s0, data_fn, 0.05, checkpoint_dir=ck,
                                     **kw)
    x = np.asarray(jax.tree.leaves(st.x)[0])
    check(all(np.isfinite(np.asarray(v)).all()
              for v in jax.tree.leaves(st.x)), "(b) state not finite")
    check(_bit_equal(st.x, st_ref.x), "(b) resumed state differs from the "
          "uninterrupted run")
    check(_bit_equal(m, m_ref), "(b) resumed metrics differ from the "
          "uninterrupted run")
    print(f"(b) scheduler population={sizes.population} "
          f"cohort={sizes.cohort} rounds={sizes.sched_rounds}: crashed at "
          f"round {kill}, resumed from {os.path.basename(snaps[-1])}; "
          f"state and {len(m)} metric rows bit-identical to the "
          f"uninterrupted run (|x|max {float(np.abs(x).max()):.4f})",
          flush=True)
    print(f"(b) PASS  (setup: {seconds:.1f}s wall for the reference run "
          f"incl. compile)", flush=True)


# ---------------------------------------------------------------------------
# (c) the wire kernels and the LM trainer
# ---------------------------------------------------------------------------

def phase_kernels(sizes: Sizes):
    """The grouped encode (both dithers), quantize->dequantize and
    decode_reduce kernels at a whisper-base leaf width with 256-wide
    groups, against the jnp oracles of the same wire format."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import compression as C
    from repro.kernels import ops, ref

    R, D, g = sizes.kernel_rows, sizes.kernel_cols, 256
    G = D // g
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (R, D))
    u = C.hash_dither(key, (R, D))
    route = _route(ops.quantize_encode_grouped.lower(
        x, u, bits=8, group=g).compile())
    codes, scales = ops.quantize_encode_grouped(x, u, bits=8, group=g)
    rc, rs = ref.encode_groups_ref(x.reshape(R, G, g), u.reshape(R, G, g),
                                   bits=8)
    check(np.array_equal(np.asarray(scales), np.asarray(rs).reshape(R, G)),
          "(c) encode kernel scales differ from the oracle")
    dc = np.abs(np.asarray(codes, np.int32)
                - np.asarray(rc, np.int32).reshape(R, D))
    print(f"(c) encode kernel ({route}) vs oracle {(R, D)} group {g}: "
          f"scales bit-equal, codes differ at {int((dc > 0).sum())} of "
          f"{dc.size} (max {int(dc.max())} level)", flush=True)
    check(dc.max() <= 1 and (dc > 0).mean() <= 1e-4,
          "(c) encode kernel codes differ from the oracle")

    # in-kernel dither: apply == decode(encode) on the chip too (shared
    # tiling and draw order), within one quantization level, unbiased
    seed = C.fold_seed(key)
    kc, ks = ops.quantize_encode_kernel_dither(x, seed, bits=8, group=g)
    deq = ref.decode_groups_ref(kc.reshape(R, G, g), ks.reshape(R, G, 1),
                                bits=8).reshape(R, D)
    app = ops.quantize_dequantize_kernel_dither(x, seed, bits=8, group=g)
    check(np.array_equal(np.asarray(deq), np.asarray(app)),
          "(c) kernel-dither apply != decode(encode)")
    level = jnp.repeat(ks, g, axis=-1) / 127.0
    err = deq - x
    check(bool(jnp.all(jnp.abs(err) <= level * (1 + 1e-6))),
          "(c) kernel-dither error above one quantization level")
    bias = float(jnp.mean(err / jnp.where(level > 0, level, 1.0)))
    check(abs(bias) < 1e-2, f"(c) kernel-dither mean error {bias} levels")

    # fused decode + weighted reduce over 4 clients
    keys = jax.random.split(key, 4)
    c4, s4 = jax.vmap(lambda k: ops.quantize_encode_grouped(
        x, C.hash_dither(k, (R, D)), bits=8, group=g))(keys)
    w = jnp.asarray([0.1, 0.2, 0.3, 0.4], jnp.float32)
    fused = ops.dequantize_reduce_grouped(c4, s4, w, bits=8, group=g)
    plain = C.weighted_sum(w, ref.decode_groups_ref(
        c4.reshape(4, R, G, g), s4.reshape(4, R, G, 1),
        bits=8).reshape(4, R, D))
    dr = float(jnp.max(jnp.abs(fused - plain)))
    print(f"(c) kernel dither: apply == decode(encode), mean error "
          f"{bias:+.2e} levels; decode_reduce C=4 vs oracle max abs diff "
          f"{dr:.3e}", flush=True)
    check(dr <= 1e-6 * float(jnp.max(jnp.abs(plain))),
          "(c) decode_reduce kernel differs from the oracle")
    print("(c) kernels PASS", flush=True)


def expected_route(params, block: int) -> str:
    """The wire route the shard-safe compressor takes on one device: the
    kernel for leaves of at least ``KERNEL_DISPATCH_MIN`` elements whose
    last-axis group is 128-aligned, jnp for the rest."""
    import jax

    from repro.core import compression as C
    return "kernel" if any(
        leaf.ndim and leaf.size >= C.KERNEL_DISPATCH_MIN
        and C.group_size(leaf.shape[-1], block) % 128 == 0
        for leaf in jax.tree.leaves(params)) else "jnp"


def phase_lm(sizes: Sizes):
    import jax
    import numpy as np

    from repro.core import compression as C
    from repro.fed.trainer import FedLMConfig
    from repro.launch import train

    argv = ["--arch", "whisper-base", "--preset", sizes.lm_preset,
            "--clients", str(sizes.lm_clients), "--batch",
            str(sizes.lm_batch), "--seq", str(sizes.lm_seq), "--log-every",
            "1", "--steps"]
    res = train.main(argv + [str(sizes.lm_steps)])
    # the entry point once more, with the in-memory caches cleared: its
    # compile either reads the persistent cache or compiles again
    jax.clear_caches()
    again = train.main(argv + ["1"])
    print(f"(c) setup: train step compile {res.compile_seconds:.2f}s, then "
          f"{again.compile_seconds:.2f}s in a second run after clearing the "
          f"in-memory caches (a persistent-cache hit shows as much smaller); "
          f"{res.step_seconds:.3f}s per step; set-up information, not "
          f"metrics", flush=True)
    check(all(math.isfinite(v) for v in res.losses),
          f"(c) loss not finite: {res.losses}")
    route = _route(res.compiled)
    block = FedLMConfig.quant_block
    expect = expected_route(res.state.s_hat, block)
    groups = sorted({C.group_size(leaf.shape[-1], block)
                     for leaf in jax.tree.leaves(res.state.s_hat)
                     if leaf.ndim})
    print(f"(c) LM trainer whisper-base/{sizes.lm_preset}: losses "
          f"{[round(v, 4) for v in res.losses]}; wire route {route}, "
          f"expected {expect} (shard-safe groups {groups})", flush=True)
    check(route == expect, f"(c) the compiled train step took the {route} "
          f"wire route, the compressor predicts {expect}")
    check(all(np.isfinite(np.asarray(v, np.float32)).all()
              for v in jax.tree.leaves(res.state.s_hat)),
          "(c) params not finite")
    print("(c) PASS", flush=True)


# ---------------------------------------------------------------------------
# --four-chips
# ---------------------------------------------------------------------------

def four_chips(sizes: Sizes, devices):
    import numpy as np

    check(len(devices) >= 4, f"--four-chips needs 4 devices, have "
          f"{len(devices)}")
    dl, host = paper_inputs()
    one = paper_run(dl, host, devices[0], sizes)
    print(f"(4) one-device reference on device {devices[0].id}: objective "
          f"{float(np.asarray(one[1]['loss'])[-1]):.6f}", flush=True)
    paper_client_mesh(sizes, devices[:4], dl, host, one)
    paper_edge_mesh(sizes, devices[:4], dl, host, one)
    lm_physical_vs_logical(sizes, devices[:4])
    print("(4) PASS", flush=True)


def paper_client_mesh(sizes: Sizes, devices, dl, host, one):
    """The paper path on a 4-device client mesh, gather and reduce, against
    one device. On the CPU the gather run is bit-identical to one device
    (tests/test_sharded_driver.py); XLA:TPU compiles the 20-client program
    and the 5-clients-per-chip one to float32 results a few ulps apart, so
    on the chip the participation draws and the byte accounting must be
    bit-equal and the objective within ``MESH_RTOL``."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.compression import KERNEL_DISPATCH_MIN

    st1, h1 = one
    mesh = Mesh(np.asarray(devices), ("clients",))
    print(f"(4a) client mesh devices: "
          f"{sorted(d.id for d in mesh.devices.flat)}", flush=True)
    stg, hg = paper_run(dl, host, devices[0], sizes, mesh=mesh,
                        client_axis="clients", uplink="gather")
    biggest = max(leaf.size for leaf in jax.tree.leaves(host[2]))
    print(f"(4a) gather: objective {float(np.asarray(hg['loss'])[-1]):.6f}; "
          f"wire route "
          f"{'kernel' if biggest >= KERNEL_DISPATCH_MIN else 'jnp'} "
          f"(largest leaf {biggest} elements); client variates on devices "
          f"{_device_ids(stg.v_i)}", flush=True)
    la, lb = jax.tree.leaves(stg), jax.tree.leaves(st1)
    differ = sum(int((np.asarray(a) != np.asarray(b)).sum())
                 for a, b in zip(la, lb))
    rel = _objective_rel(hg, h1)
    print(f"(4a) gather vs one device: {differ} of "
          f"{sum(a.size for a in la)} state elements differ; objective rel "
          f"diff {rel:.3e} (limit {MESH_RTOL:g})", flush=True)
    check(_bit_equal({k: hg[k] for k in ACCOUNTING}, {k: h1[k]
                                                       for k in ACCOUNTING}),
          "(4a) gather: participation or byte accounting differs")
    check(rel <= MESH_RTOL, "(4a) gather mesh run not allclose")
    _, hr = paper_run(dl, host, devices[0], sizes, mesh=mesh,
                      client_axis="clients", uplink="reduce")
    rel = _objective_rel(hr, h1)
    print(f"(4a) reduce vs one device: objective rel diff {rel:.3e} (limit "
          f"{MESH_RTOL:g})", flush=True)
    check(rel <= MESH_RTOL, "(4a) reduce mesh run not allclose")


def paper_edge_mesh(sizes: Sizes, devices, dl, host, one):
    """Two-tier on a 2 edges x 2 clients mesh against the flat run."""
    from repro.api import Topology
    from repro.launch.mesh import make_edge_mesh

    emesh = make_edge_mesh(2, 2, devices=devices)
    print(f"(4b) edge mesh {dict(emesh.shape)} devices: "
          f"{[[d.id for d in row] for row in emesh.devices]}", flush=True)
    st2, h2 = paper_run(dl, host, devices[0], sizes, mesh=emesh,
                        client_axis="client",
                        spec_kw=dict(topology=Topology.two_tier(2)))
    rel = _objective_rel(h2, one[1])
    print(f"(4b) two-tier 2x2 vs flat one device: objective rel diff "
          f"{rel:.3e} (limit {MESH_RTOL:g}); client variates on "
          f"devices {_device_ids(st2.v_i)}", flush=True)
    check(rel <= MESH_RTOL, "(4b) two-tier run not allclose to flat")


def lm_physical_vs_logical(sizes: Sizes, devices):
    """whisper-base with one silo per chip (physical, reduce uplink) against
    the same clients run logically on one chip."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import repro.configs as RC
    from repro.data.synthetic import token_stream
    from repro.fed import trainer as FT
    from repro.launch.train import preset_config
    from repro.models.model import build_model

    cfg = preset_config(RC.get("whisper-base"), sizes.lm_preset)
    model = build_model(cfg)
    n = len(devices)
    b_local = sizes.lm_batch // n
    key = jax.random.PRNGKey(0)
    mesh = Mesh(np.asarray(devices), ("clients",))

    def batch_at(k):
        k1, k2 = jax.random.split(k)
        toks = jax.vmap(lambda kk: token_stream(
            kk, b_local, sizes.lm_seq + 1, cfg.vocab))(jax.random.split(k1, n))
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:],
                "frames": jax.random.normal(
                    k2, (n, b_local, cfg.n_frontend_tokens, cfg.d_model))
                * 0.02}

    runs = {}
    for mode in ("logical", "physical"):
        fcfg = FT.FedLMConfig(n_clients=n, rho=0.05, client_mode=mode)
        with jax.default_device(devices[0]):
            state = FT.init_state(model, key, fcfg)
            step = FT.make_train_step(
                model, fcfg, **(dict(mesh=mesh, uplink="reduce")
                                if mode == "physical" else {}))
            jitted, k, losses = jax.jit(step), key, []
            for t in range(sizes.mesh_lm_steps):
                k, kb, ks = jax.random.split(k, 3)
                args = (state, batch_at(kb), ks, np.float32(0.5))
                state, m = jitted(*args)
                losses.append(float(m["loss"]))
            # the last step's program again: a compile-cache hit
            route = _route(jitted.lower(*args).compile())
        runs[mode] = losses
        print(f"(4c) LM {mode}: losses {[round(v, 5) for v in losses]}, "
              f"wire route {route}, client variates on devices "
              f"{_device_ids(state.v_i)}", flush=True)
    rel = max(_rel(a, b) for a, b in zip(runs["physical"], runs["logical"]))
    check(all(math.isfinite(v) for v in runs["physical"]),
          "(4c) physical loss not finite")
    print(f"(4c) physical vs logical: loss rel diff {rel:.3e} (limit "
          f"{LM_MESH_RTOL:g})", flush=True)
    check(rel <= LM_MESH_RTOL, "(4c) physical and logical losses differ")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh paths")
    args = ap.parse_args(argv)
    if not os.path.isfile(os.path.join(HERE, "src", "repro", "launch",
                                       "cache.py")):
        print("chip_smoke: no repro checkout next to this script",
              file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(HERE, "src"), HERE]
    # the host-CPU reference needs JAX's CPU backend beside the TPU
    if os.environ.get("JAX_PLATFORMS") == "tpu":
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    import jax

    from repro.launch.cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's default device is "
              f"{dev.platform})", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind}, {len(devices)} device(s), "
          f"jax {jax.__version__}", flush=True)
    print(f"setup: compile cache at {cache}", flush=True)
    sizes = Sizes()
    try:
        if args.four_chips:
            four_chips(sizes, devices)
        else:
            dl, host = phase_paper(sizes, dev)
            phase_scheduler(sizes, dl, host, dev)
            phase_kernels(sizes)
            phase_lm(sizes)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
