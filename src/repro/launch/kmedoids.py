"""Bandit k-medoids driver — the clustering workload as a service entry.

Runs :func:`repro.api.kmedoids` (BUILD -> ragged per-cluster
refinement -> bandit SWAP) on a planted-cluster dataset, reports ARI against
the planted labels plus the full pull breakdown, and optionally compares
against exact PAM (``--compare``; pull ratio is always reported — exact
PAM's count is ``n^2`` by construction, no run needed). ``--serve`` routes
the refinement sweeps through the continuous-batching
:class:`repro.launch.serve_medoid.MedoidServer` instead of direct ragged
dispatches, sharing buckets with any other medoid traffic. ``--trace`` /
``--metrics-out`` attach the observability layer (:mod:`repro.obs`):
JSONL span/round/select events and a Prometheus text exposition.

Example:
  PYTHONPATH=src python -m repro.launch.kmedoids --k 8 --n 4096 --d 128 \
      --dataset rnaseq_like --backend pallas_fused
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time

import jax

from repro.api import KMedoidsConfig, kmedoids
from repro.cluster import adjusted_rand_index, pam_exact, pam_pulls
from repro.core import list_backends
from repro.data.medoid_datasets import CLUSTER_DATASETS
from repro.engine.programs import device_info, enable_compile_cache


def run(n: int, d: int, k: int, dataset: str, *, metric: str = "",
        backend: str = "reference", seed: int = 0,
        build_budget_per_arm: int = 16, swap_budget_per_arm: int = 16,
        refine_budget_per_arm: int = 20, refine_sweeps: int = 1,
        max_swap_rounds: int = 8, compare: bool = False,
        serve: bool = False, trace=None,
        metrics_path: str | None = None) -> dict:
    if dataset not in CLUSTER_DATASETS:
        raise ValueError(f"unknown dataset {dataset!r}; "
                         f"one of {sorted(CLUSTER_DATASETS)}")
    ds_metric, gen = CLUSTER_DATASETS[dataset]
    metric = metric or ds_metric
    key = jax.random.key(seed)
    data, labels = gen(jax.random.fold_in(key, 0), n, d, k)

    cfg = KMedoidsConfig(metric=metric, backend=backend,
                         build_budget_per_arm=build_budget_per_arm,
                         swap_budget_per_arm=swap_budget_per_arm,
                         refine_budget_per_arm=refine_budget_per_arm,
                         refine_sweeps=refine_sweeps,
                         max_swap_rounds=max_swap_rounds)
    t0 = time.time()
    span = (trace.span("kmedoids", n=n, k=k, mode="serve" if serve
                       else "direct") if trace is not None
            else contextlib.nullcontext())
    srv = None
    with span:
        if serve:
            from repro.cluster import kmedoids_via_service
            from repro.launch.serve_medoid import MedoidServer
            # trace-aware server: refinement dispatches emit round/select
            # events (and run the telemetry program variant)
            srv = MedoidServer(metric=cfg.metric, backend=cfg.backend,
                               budget_per_arm=cfg.refine_budget_per_arm,
                               trace=trace)
            res, srv = kmedoids_via_service(
                data, k, jax.random.fold_in(key, 1), server=srv,
                metric=cfg.metric, backend=cfg.backend,
                build_budget_per_arm=cfg.build_budget_per_arm,
                swap_budget_per_arm=cfg.swap_budget_per_arm,
                refine_budget_per_arm=cfg.refine_budget_per_arm,
                refine_sweeps=cfg.refine_sweeps,
                max_swap_rounds=cfg.max_swap_rounds)
            serve_stats = srv.stats()
        else:
            res = kmedoids(data, k, jax.random.fold_in(key, 1), config=cfg)
            serve_stats = None
    wall = time.time() - t0

    out = {
        "n": n, "d": d, "k": k, "dataset": dataset, "metric": metric,
        "backend": backend, "mode": "serve" if serve else "direct",
        **device_info(),
        "medoids": res.medoids, "cost": round(res.cost, 3),
        "ari": round(adjusted_rand_index(res.labels, labels), 4),
        "pulls": res.pulls,
        "pulls_breakdown": {"build": res.build_pulls,
                            "assign": res.assign_pulls,
                            "refine": res.refine_pulls,
                            "swap": res.swap_pulls},
        "swaps": res.swaps, "refine_updates": res.refine_updates,
        "pam_pulls": pam_pulls(n),
        "pulls_ratio": round(pam_pulls(n) / max(1, res.pulls), 2),
        "wall_s": round(wall, 2),
    }
    if serve_stats is not None:
        out["serve"] = serve_stats
    if metrics_path:
        # --serve gets the per-bucket server metrics; the direct path still
        # has the engine odometers to expose
        from repro.obs import instrument_exposition
        with open(metrics_path, "w") as fh:
            fh.write(srv.exposition() if srv is not None
                     else instrument_exposition())
    if compare:
        t0 = time.time()
        pam = pam_exact(data, k, metric)
        out["pam"] = {
            "medoids": pam.medoids, "cost": round(pam.cost, 3),
            "ari": round(adjusted_rand_index(pam.labels, labels), 4),
            "swaps": pam.swaps, "wall_s": round(time.time() - t0, 2),
        }
        out["cost_vs_pam"] = round(res.cost / max(pam.cost, 1e-12), 4)
        out["ari_vs_pam"] = round(
            adjusted_rand_index(res.labels, pam.labels), 4)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--dataset", default="rnaseq_like",
                    choices=sorted(CLUSTER_DATASETS))
    ap.add_argument("--metric", default="",
                    choices=["", "l1", "l2", "sql2", "cosine"])
    ap.add_argument("--backend", default="reference",
                    choices=list(list_backends()))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--build-budget-per-arm", type=int, default=16)
    ap.add_argument("--swap-budget-per-arm", type=int, default=16)
    ap.add_argument("--refine-budget-per-arm", type=int, default=20)
    ap.add_argument("--refine-sweeps", type=int, default=1)
    ap.add_argument("--max-swap-rounds", type=int, default=8)
    ap.add_argument("--compare", action="store_true",
                    help="also run exact PAM (O(n^2) — keep n modest)")
    ap.add_argument("--serve", action="store_true",
                    help="route refinement through the MedoidServer")
    ap.add_argument("--trace", default=None, metavar="PATH", dest="trace_out",
                    help="stream span/round/select events to this JSONL "
                         "file (with --serve, refinement dispatches run "
                         "with device-resident telemetry)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a Prometheus text exposition on exit (the "
                         "server's per-bucket metrics with --serve, the "
                         "engine odometers otherwise)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    session = None
    if args.trace_out:
        from repro.obs import TraceSession
        session = TraceSession(args.trace_out, meta={
            "workload": "kmedoids", "backend": args.backend,
            "n": args.n, "k": args.k, "seed": args.seed})
    try:
        print(json.dumps(run(
            args.n, args.d, args.k, args.dataset, metric=args.metric,
            backend=args.backend, seed=args.seed,
            build_budget_per_arm=args.build_budget_per_arm,
            swap_budget_per_arm=args.swap_budget_per_arm,
            refine_budget_per_arm=args.refine_budget_per_arm,
            refine_sweeps=args.refine_sweeps,
            max_swap_rounds=args.max_swap_rounds,
            compare=args.compare, serve=args.serve, trace=session,
            metrics_path=args.metrics_out)))
    finally:
        if session is not None:
            session.close()


if __name__ == "__main__":
    main()
