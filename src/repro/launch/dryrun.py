import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape x mesh) combination, lower + compile
the real step function — the FedMM train step for train_4k, serve prefill /
decode for the inference shapes — against the production mesh with
ShapeDtypeStruct stand-ins (no allocation), then record:

  * compiled.memory_analysis()  (per-device bytes -> proves it fits)
  * compiled.cost_analysis()    (FLOPs / bytes for the roofline)
  * collective bytes parsed from the partitioned HLO (roofline 3rd term)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-medium-14b \
      --shape train_4k [--multi-pod] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun_all.json
"""
import argparse
import dataclasses
import json
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs as C
from repro.configs.base import INPUT_SHAPES
from repro.fed import trainer as FT
from repro.launch import mesh as M
from repro.launch.roofline import (analytic_bytes, hlo_accounting,
                                   roofline_terms, model_flops_estimate)
from repro.models import sharding as shd
from repro.models.model import build_model


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _tree_sds(shapes_tree, specs_tree, mesh):
    return jax.tree.map(
        lambda l, s: _sds(l.shape, l.dtype, mesh, s), shapes_tree, specs_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def input_specs(cfg, shape, mesh, fed_cfg=None, n_clients=None):
    """ShapeDtypeStruct stand-ins for every model input of this shape.
    Training inputs carry the leading client dim (FedMM batch contract)."""
    multi = "pod" in mesh.axis_names
    batch_axes = M.client_axes(multi)
    bs = int(np.prod([mesh.shape[a] for a in batch_axes]))
    GB, S = shape.global_batch, shape.seq_len

    if shape.kind == "train":
        n = n_clients
        b_local = GB // n
        bspec = FT.batch_spec(fed_cfg, batch_axes)
        out = {
            "tokens": _sds((n, b_local, S), jnp.int32, mesh, bspec),
            "labels": _sds((n, b_local, S), jnp.int32, mesh, bspec),
        }
        fs = P(*(list(bspec) + [None]))
        if cfg.family == "vlm":
            out["patches"] = _sds((n, b_local, cfg.n_frontend_tokens,
                                   cfg.d_model), jnp.float32, mesh, fs)
        elif cfg.family == "audio":
            out["frames"] = _sds((n, b_local, cfg.n_frontend_tokens,
                                  cfg.d_model), jnp.float32, mesh, fs)
        return out

    bspec = P(batch_axes if GB % bs == 0 else None, None)
    out = {"tokens": _sds((GB, S), jnp.int32, mesh, bspec),
           "labels": _sds((GB, S), jnp.int32, mesh, bspec)}
    if cfg.family == "vlm":
        out["patches"] = _sds((GB, cfg.n_frontend_tokens, cfg.d_model),
                              jnp.float32, mesh, P(bspec[0], None, None))
    elif cfg.family == "audio":
        out["frames"] = _sds((GB, cfg.n_frontend_tokens, cfg.d_model),
                             jnp.float32, mesh, P(bspec[0], None, None))
    return out


def compile_one(arch_id: str, shape_name: str, multi_pod: bool,
                overrides=None, variant=None):
    """Lower + compile one combination; returns a metrics dict.

    ``variant`` (perf-iteration levers, §Perf):
      kv_dtype="int8"        quantized KV cache (decode shapes)
      attn_mode="replicated" attention weights replicated over 'model' (train)
      use_cv=False           drop control variates (alpha=0 regime)
      quant_bits=<n>         FedMM uplink quantization width (0 = off)
      n_clients=<n>          override the client layout
    """
    variant = variant or {}
    cfg = C.get(arch_id)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if "kv_dtype" in variant:
        cfg = dataclasses.replace(cfg, kv_dtype=variant["kv_dtype"])
    if "moe_group" in variant:
        cfg = dataclasses.replace(cfg, moe_group=variant["moe_group"])
    shape = INPUT_SHAPES[shape_name]

    if shape_name == "long_500k" and not cfg.is_subquadratic:
        return {"arch": arch_id, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped",
                "reason": "full-attention arch; long_500k requires "
                          "sub-quadratic attention (DESIGN.md §4)"}

    mesh = M.make_production_mesh(multi_pod=multi_pod)
    multi = multi_pod
    batch_axes = M.client_axes(multi)
    fsdp_size = int(np.prod([mesh.shape[a] for a in batch_axes]))
    n_chips = int(np.prod(list(mesh.shape.values())))
    model = build_model(cfg)
    shd.install_rules(M.axis_rules(multi))

    try:
        params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        n_params = sum(int(np.prod(l.shape)) if l.shape else 1
                       for l in jax.tree.leaves(params_shapes))

        if shape.kind == "train":
            n_clients, mode = FT.choose_client_layout(n_params, multi)
            n_clients = variant.get("n_clients", n_clients)
            fed_cfg = FT.FedLMConfig(
                n_clients=n_clients, client_mode=mode,
                use_cv=variant.get("use_cv", True),
                alpha=0.0 if not variant.get("use_cv", True) else 0.1,
                quant_bits=variant.get("quant_bits", 8),
                attn_mode=variant.get("attn_mode", "sharded"),
                mlp_mode=variant.get("mlp_mode", "generic"))
            sspec, vspec, vispec = FT.state_specs(
                params_shapes, fed_cfg, fsdp=batch_axes, fsdp_size=fsdp_size)
            use_cv = fed_cfg.use_cv
            state_sds = FT.FedLMState(
                s_hat=_tree_sds(params_shapes, sspec, mesh),
                v=_tree_sds(params_shapes, vspec, mesh) if use_cv else {},
                v_i=jax.tree.map(
                    lambda l, s: _sds((n_clients,) + l.shape, l.dtype, mesh, s),
                    params_shapes, vispec,
                    is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
                if use_cv else {},
                step=_sds((), jnp.int32, mesh, P()))
            batch_sds = input_specs(cfg, shape, mesh, fed_cfg, n_clients)
            key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
            step_fn = FT.make_train_step(model, fed_cfg)
            fn = lambda st, b, k: step_fn(st, b, k, 0.1)
            donate = (0,)   # state buffers alias in place
            args = (state_sds, batch_sds, key_sds)
            extra = {"n_clients": n_clients, "client_mode": mode}
        elif shape.kind == "prefill":
            pspec = shd.param_specs(params_shapes, fsdp=batch_axes,
                                    fsdp_size=fsdp_size,
                                    attn_mode=variant.get("attn_mode", "sharded"),
                                    mlp_mode=variant.get("mlp_mode", "generic"))
            params_sds = _tree_sds(params_shapes, pspec, mesh)
            batch_sds = input_specs(cfg, shape, mesh)
            fn = lambda p, b: model.prefill(p, b)
            donate = ()
            args = (params_sds, batch_sds)
            extra = {}
        else:  # decode
            # fsdp_off (§Perf): TP-resident weights for serving — no
            # per-token FSDP weight gathers, at P_bytes/16 per device.
            p_fsdp = () if variant.get("fsdp_off") else batch_axes
            p_fsdp_size = 10**9 if variant.get("fsdp_off") else fsdp_size
            pspec = shd.param_specs(params_shapes, fsdp=p_fsdp,
                                    fsdp_size=p_fsdp_size,
                                    attn_mode=variant.get("attn_mode", "sharded"),
                                    mlp_mode=variant.get("mlp_mode", "generic"))
            params_sds = _tree_sds(params_shapes, pspec, mesh)
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cspec = shd.cache_specs(cache_shapes, batch_axes,
                                    batch_size=fsdp_size)
            cache_sds = _tree_sds(cache_shapes, cspec, mesh)
            GB = shape.global_batch
            tok_spec = P(batch_axes if GB % fsdp_size == 0 else None, None)
            tok_sds = _sds((GB, 1), jnp.int32, mesh, tok_spec)
            pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
            fn = lambda p, c, t, pos: model.decode(p, c, t, pos)
            donate = (1,)   # cache updates in place
            args = (params_sds, cache_sds, tok_sds, pos_sds)
            extra = {}

        # shardings here are explicit NamedShardings, so the ambient mesh
        # only scopes the lowering
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
            compiled = lowered.compile()
            cost = compiled.cost_analysis() or {}
            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
        acct = hlo_accounting(hlo)
        flops_dev = acct["flops"]                  # trip-count-weighted dots
        bytes_dev = analytic_bytes(               # structural HBM model
            cfg, shape, n_params,
            n_clients=extra.get("n_clients", 1),
            client_mode=extra.get("client_mode", "physical"),
            dp=fsdp_size, tp=mesh.shape["model"], n_chips=n_chips)
        mem_stats = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        } if mem is not None else {}
        terms = roofline_terms(flops_dev, bytes_dev, acct["collective_bytes"],
                               n_chips=n_chips)
        mf = model_flops_estimate(cfg, shape, n_params)
        result = {
            "arch": arch_id, "shape": shape_name, "multi_pod": multi_pod,
            "status": "ok", "n_params": n_params, "n_chips": n_chips,
            "flops_per_device": flops_dev, "bytes_per_device": bytes_dev,
            "hlo_traffic_proxy_bytes": acct["traffic_bytes"],
            "collective_bytes_per_device": acct["collective_bytes"],
            "collectives": acct["by_kind"],
            "collective_counts": acct["counts"],
            "xla_cost_analysis": {"flops": float(cost.get("flops", 0.0)),
                                  "bytes": float(cost.get("bytes accessed", 0.0))},
            "memory": mem_stats, "roofline": terms,
            "model_flops": mf,
            "useful_flops_ratio": (mf / (flops_dev * n_chips)
                                   if flops_dev else None),
            **extra,
        }
        return result
    except (ValueError, TypeError, NotImplementedError, RuntimeError) as e:
        # compile/lowering failures only (shape errors, unsupported ops,
        # XlaRuntimeError/Mosaic are RuntimeError subclasses): those are a
        # sweep RESULT. Anything else — KeyboardInterrupt, OOM kills,
        # our own bugs (AttributeError/KeyError/...) — propagates
        return {"arch": arch_id, "shape": shape_name, "multi_pod": multi_pod,
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}
    finally:
        shd.install_rules(None)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=C.ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) on this mesh")
    ap.add_argument("--archs", default=None,
                    help="comma-separated arch subset (with --all)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = args.archs.split(",") if args.archs else C.ARCH_IDS
    combos = ([(a, s) for a in archs for s in INPUT_SHAPES]
              if args.all else [(args.arch, args.shape)])
    results = []
    for arch, shape in combos:
        r = compile_one(arch, shape, args.multi_pod)
        results.append(r)
        status = r["status"]
        brief = (f"{arch:28s} {shape:12s} pod={2 if args.multi_pod else 1} "
                 f"{status}")
        if status == "ok":
            t = r["roofline"]
            brief += (f"  mem={r['memory'].get('temp_bytes', 0)/2**30:.2f}GiB "
                      f"compute={t['compute_s']:.4f}s "
                      f"hbm={t['memory_s']:.4f}s ici={t['collective_s']:.4f}s "
                      f"-> {t['dominant']}")
        elif status == "error":
            brief += "  " + r["error"][:120]
        print(brief, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    bad = [r for r in results if r["status"] == "error"]
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
