"""Medoid engine driver — the paper's algorithm as a service.

Runs Correlated Sequential Halving (single-device or distributed over
whatever mesh exists), with per-round survivor checkpointing so a preempted
job restarts mid-algorithm (rounds are idempotent given (seed, round)).

``--backend`` selects the distance implementation from the registry in
``repro.core.backend`` (reference | pallas_pairwise | pallas_fused |
pallas_fused_topk); ``--batch B`` answers B independent queries in one
dispatch via ``repro.api.find_medoids_batch``. All modes are thin wrappers
over the :mod:`repro.api` facade.

Observability (:mod:`repro.obs`): ``--trace PATH`` runs the query with
device-resident round telemetry (bit-identical answers, same single
dispatch) and streams span / round / select events to JSONL;
``--metrics-out PATH`` writes the engine odometers as a Prometheus text
exposition; ``--profile-dir DIR`` brackets the run in
``jax.profiler.start_trace``/``stop_trace`` with the bandit phases
annotated onto the profiler timeline.

Example:
  PYTHONPATH=src python -m repro.launch.medoid --n 4096 --d 512 \
      --metric l1 --budget-per-arm 30 --dataset rnaseq20k_like \
      --backend pallas_fused --batch 8 --trace /tmp/medoid_trace.jsonl
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.api import find_medoid, find_medoids_batch
from repro.checkpoint import manager as ckpt
from repro.core import (exact_medoid, list_backends, rand_medoid,
                        round_schedule, schedule_pulls)
from repro.core.distributed import make_row_sharding
from repro.data.medoid_datasets import DATASETS, planted_medoid
from repro.engine.programs import device_info, enable_compile_cache
from repro.runtime.fault_tolerance import elastic_remesh


def run(n: int, d: int, metric: str, budget_per_arm: int, dataset: str,
        *, seed: int = 0, use_kernel: bool = False, distributed: bool = False,
        compare: bool = False, ckpt_dir: str | None = None,
        backend: str = "reference", batch: int = 0, trace=None,
        precision: str = "fp32") -> dict:
    key = jax.random.key(seed)
    if use_kernel and backend == "reference":
        backend = "pallas_pairwise"   # legacy flag -> kernel-backed blocks

    def gen_data(k):
        if dataset in DATASETS:
            return DATASETS[dataset][1](k, n, d)
        return planted_medoid(k, n, d)

    if dataset in DATASETS:
        metric = metric or DATASETS[dataset][0]
    else:
        metric = metric or "l2"
    if batch > 0 and distributed:
        raise ValueError("--batch and --distributed are mutually exclusive; "
                         "the batched engine is single-host (vmap)")
    if distributed and len(jax.devices()) < 2:
        raise ValueError(f"--distributed needs at least 2 devices, found "
                         f"{len(jax.devices())}")
    data = None if batch > 0 else gen_data(key)

    budget = budget_per_arm * n
    sched = round_schedule(n, budget)
    out = {"n": n, "d": d, "metric": metric, "budget": budget,
           "backend": backend, "precision": precision, **device_info(),
           "pulls_scheduled": schedule_pulls(n, budget),
           "rounds": [(r.survivors, r.num_refs) for r in sched]}

    cfg_kw = dict(metric=metric, backend=backend,
                  budget_per_arm=budget_per_arm, precision=precision)
    if distributed and precision != "fp32":
        raise ValueError("--precision requires the single-host engine; "
                         "run without --distributed")
    # --trace: switch the facade to the telemetry-carrying program variant
    # (answers stay bit-identical; the distributed engine isn't instrumented)
    with_tel = trace is not None and not distributed
    dispatch_span = (trace.span("dispatch", mode=out.get("mode", backend))
                     if trace is not None else contextlib.nullcontext())
    t0 = time.time()
    with dispatch_span:
        if batch > 0:
            # multi-query mode: B independent candidate sets, one dispatch
            batch_data = jnp.stack([gen_data(jax.random.fold_in(key, 100 + b))
                                    for b in range(batch)])
            res = find_medoids_batch(batch_data, jax.random.fold_in(key, 1),
                                     telemetry=with_tel, **cfg_kw)
            medoids, tel = res if with_tel else (res, None)
            out["mode"] = f"batch x{batch} ({backend})"
            out["medoids"] = [int(m) for m in medoids]
            medoid = out["medoids"][0]
            data = batch_data[0]
            if trace is not None and tel is not None:
                for slot, m in enumerate(out["medoids"]):
                    trace.record_rounds(tel, slot=slot, slot_id=slot)
                    trace.event("select", winner=m,
                                pulls=int(tel["pulls"][slot].sum()), n=n,
                                algo="corr_sh", metric=metric,
                                backend=backend, slot_id=slot)
        elif distributed:
            mesh = elastic_remesh(preferred_tp=1)
            data_sh = jax.device_put(data, make_row_sharding(mesh))
            medoid = find_medoid(data_sh, jax.random.fold_in(key, 1),
                                 mesh=mesh, distributed_impl="v2",
                                 **cfg_kw).medoid
            out["mode"] = f"distributed-v2 x{len(jax.devices())} ({backend})"
        else:
            res = find_medoid(data, jax.random.fold_in(key, 1),
                              telemetry=with_tel, **cfg_kw)
            medoid = res.medoid
            out["mode"] = backend
            if precision != "fp32":
                # True: the quantized certificate held; False: the answer
                # came from the exact fp32 fallback (exact either way)
                out["verified"] = res.verified
            if trace is not None:
                trace.record_result(res)
    out["medoid"] = medoid
    out["corrsh_s"] = round(time.time() - t0, 3)
    if with_tel and batch == 0:
        out["telemetry"] = {k: v.tolist()
                            for k, v in (res.telemetry or {}).items()}

    if ckpt_dir:
        ckpt.save(ckpt_dir, 0, {"medoid": jnp.asarray(medoid)},
                  extra={"n": n, "metric": metric, "budget": budget})

    if compare:
        t0 = time.time()
        truth = int(exact_medoid(data, metric))
        out["exact"] = truth
        out["exact_s"] = round(time.time() - t0, 3)
        out["correct"] = truth == medoid
        t0 = time.time()
        out["rand"] = int(rand_medoid(data, jax.random.fold_in(key, 2),
                                      num_refs=min(n, 1000), metric=metric))
        out["rand_s"] = round(time.time() - t0, 3)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--d", type=int, default=512)
    ap.add_argument("--metric", default="", choices=["", "l1", "l2", "sql2", "cosine"])
    ap.add_argument("--budget-per-arm", type=int, default=30)
    ap.add_argument("--dataset", default="planted",
                    choices=["planted"] + list(DATASETS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--use-kernel", action="store_true",
                    help="legacy alias for --backend pallas_pairwise")
    ap.add_argument("--backend", default="reference",
                    choices=list(list_backends()))
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "bf16", "int8"],
                    help="distance precision: quantized Gram backends with "
                         "margin-widened halving and exact fp32 survivor "
                         "verification (answers stay fp32-exact)")
    ap.add_argument("--batch", type=int, default=0,
                    help="answer B independent queries in one dispatch")
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--compare", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--trace", default=None, metavar="PATH", dest="trace_out",
                    help="stream span/round/select events to this JSONL "
                         "file (runs with device-resident telemetry; "
                         "answers stay bit-identical)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the engine trace/dispatch odometers as a "
                         "Prometheus text exposition on exit")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="bracket the run in jax.profiler.start_trace/"
                         "stop_trace writing here (bandit phases annotated)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    session = None
    if args.trace_out or args.profile_dir:
        from repro.obs import TraceSession
        session = TraceSession(args.trace_out,
                               annotate=args.profile_dir is not None,
                               profiler_dir=args.profile_dir,
                               meta={"workload": "medoid",
                                     "backend": args.backend, "n": args.n,
                                     "d": args.d, "seed": args.seed})
    try:
        print(json.dumps(run(args.n, args.d, args.metric,
                             args.budget_per_arm,
                             args.dataset, seed=args.seed,
                             use_kernel=args.use_kernel,
                             distributed=args.distributed,
                             compare=args.compare,
                             ckpt_dir=args.ckpt_dir, backend=args.backend,
                             batch=args.batch, trace=session,
                             precision=args.precision)))
    finally:
        if session is not None:
            session.close()
        if args.metrics_out:
            from repro.obs import instrument_exposition
            with open(args.metrics_out, "w") as fh:
                fh.write(instrument_exposition())


if __name__ == "__main__":
    main()
