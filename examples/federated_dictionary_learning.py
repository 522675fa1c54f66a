"""Section 6 experiment driver: federated dictionary learning with FedMM.

All three data settings (synthetic homogeneous / heterogeneous /
MovieLens-like), both algorithms (FedMM and naive Theta-aggregation), with
the paper's knobs exposed: participation, quantization bits, control-variate
stepsize alpha, and the gamma_t = beta/sqrt(beta+t) schedule.

    PYTHONPATH=src python examples/federated_dictionary_learning.py \
        --setting synth_heterogeneous --rounds 150 --alpha 0.01 --bits 8
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro import api
from repro.configs.dictlearn import (MOVIELENS, SYNTH_HETEROGENEOUS,
                                     SYNTH_HOMOGENEOUS)
from repro.core import compression
from repro.core.variational import make_dictlearn
from repro.data.synthetic import client_minibatch_fn
from repro.launch.cache import enable_compile_cache

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.fig1_dictlearn import make_setting  # noqa: E402

SETTINGS = {e.name: e for e in
            (SYNTH_HOMOGENEOUS, SYNTH_HETEROGENEOUS, MOVIELENS)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--setting", default="synth_heterogeneous",
                    choices=list(SETTINGS))
    ap.add_argument("--rounds", type=int, default=150)
    ap.add_argument("--alpha", type=float, default=0.01)
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--participation", type=float, default=0.5)
    ap.add_argument("--skip-naive", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    exp = SETTINGS[args.setting]
    key = jax.random.PRNGKey(0)
    spec, clients, z = make_setting(exp, key, reduced=True)
    problem = api.as_problem(make_dictlearn(spec))
    comp = (compression.block_quant(args.bits, 128) if args.bits
            else compression.identity())
    fed = api.FederationSpec(n_clients=exp.n_clients,
                             participation=args.participation,
                             alpha=args.alpha, compressor=comp)
    batch_fn = client_minibatch_fn(clients, exp.batch_size)
    gamma = lambda t: exp.beta_stepsize / jnp.sqrt(exp.beta_stepsize + t)
    theta0 = jax.random.normal(key, (spec.p, spec.K)) * 0.1
    s0 = problem.s_bar(z[:128], theta0)

    st, hist = api.run(problem, s0, batch_fn, gamma, spec=fed, key=key,
                       n_rounds=args.rounds, eval_batch=z[:512],
                       track_mirror=True)
    hist = api.history_list(hist)
    for t in range(0, args.rounds, max(args.rounds // 10, 1)):
        h = hist[t]
        print(f"[FedMM] round {t:4d} loss={h['loss']:.4f} e_s={h['e_s']:.3e}")
    print(f"[FedMM] final loss={hist[-1]['loss']:.4f}")

    if not args.skip_naive:
        # the Section 3.1 baseline is the same driver with ONE flag flipped
        stn, hn = api.run(problem, theta0, batch_fn, gamma,
                          spec=dataclasses.replace(fed,
                                                   aggregation="parameter"),
                          key=key, n_rounds=args.rounds, eval_batch=z[:512])
        hn = api.history_list(hn)
        print(f"[naive Theta-aggregation] loss {hn[0]['loss']:.4f} -> "
              f"{hn[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
