"""Benchmark entrypoint + the shared figure harness.

``harness`` is the ONE run-loop + metrics-collection helper the three
figure reproductions (fig1/fig2/fig3) build on: it drives ``repro.api.run``
(the scan-jitted unified driver), times the trajectory, and returns the
legacy list-of-float-dicts history the figures aggregate. Each figure file
now only declares its problem, its FederationSpec(s) and its summary rows.

As an entrypoint: one function per paper figure, each printing its
summary rows. Reduced problem sizes keep the whole suite CPU-friendly; pass
--full for paper-scale settings.
"""
from __future__ import annotations

import argparse
import time

from repro import api


def harness(problem, x0, data, schedule, *, spec=None, key=None,
            rounds=None, eval_batch=None, track_mirror=False, diag=None,
            state0=None, **kw):
    """Run one trajectory on the unified driver and return
    ``(final_state, history list-of-float-dicts, seconds)``."""
    t0 = time.time()
    state, hist = api.run(api.as_problem(problem), x0, data, schedule,
                          spec=spec, key=key, n_rounds=rounds,
                          eval_batch=eval_batch, track_mirror=track_mirror,
                          diag=diag, state0=state0, **kw)
    return state, api.history_list(hist), time.time() - t0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip", default="",
                    help="comma list: fig1,fig2,fig3")
    args, _ = ap.parse_known_args()
    skip = set(args.skip.split(","))
    reduced = not args.full
    rounds = 300 if args.full else 80

    if "fig1" not in skip:
        print("=== Figure 1: aggregation space (FedMM vs naive) ===")
        from benchmarks import fig1_dictlearn
        fig1_dictlearn.main(reduced=reduced, rounds=rounds)

    if "fig2" not in skip:
        print("\n=== Figure 2: control variates ===")
        from benchmarks import fig2_control_variates
        fig2_control_variates.main(reduced=reduced, rounds=rounds)

    if "fig3" not in skip:
        print("\n=== Figure 3: FedMM-OT vs FedAdam (L2-UVP) ===")
        from benchmarks import fig3_ot
        fig3_ot.main(dims=(4, 8, 16) if reduced else (16, 32, 64),
                     rounds=40 if reduced else 100)


if __name__ == "__main__":
    main()
