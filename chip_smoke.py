#!/usr/bin/env python3
"""Bring-up smoke of the medoid engine on a TPU.

Drives the main path once through the entry points a user calls, at the
paper's data sizes, and checks every answer against the exact medoid:

* the three paper families through ``repro.launch.medoid.run`` on the
  ``pallas_fused`` backend (``correct`` against ``exact_medoid``) and again
  on the ``reference`` backend with the same key (same medoid required);
* the lowered ``pallas_fused`` programs hold compiled Mosaic kernels
  (``tpu_custom_call``), not interpreted ones;
* bf16 distances on the Netflix-like data: verified, and the fp32 medoid;
* ``MedoidServer`` on ``pallas_fused_topk`` after warmup: 16 requests, no
  recompiles, every answer its query's exact medoid;
* a live corpus (``maintain_medoid``) through inserts, a capacity doubling
  and deletes: the donating mutation programs, answer checked exactly;
* bandit k-medoids (``repro.launch.kmedoids.run``) with ARI >= 0.95.

``--chips 4`` instead runs only the sharded path: ``find_medoid(mesh=...)``
with the v2 and v1 distributed engines on the Netflix-like and RNA-Seq-like
data over four devices, against ``exact_medoid``.

Every phase prints one JSON line (wall time, XLA compile time, answer). The
last line is ``{"ok": true, "device": {...}}`` only when every phase passed;
any failure exits non-zero. Where JAX finds no TPU the script exits 2 at
once and prints no result.

Run from the root of a checkout:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.abspath(__file__))

# paper families (arXiv:1906.04356 §5): dataset -> (n, d). RNA-Seq 20k: the
# 27,998 genes of the 10x 1.3M brain cells; Netflix 20k: the 17,770 titles
# of the Netflix Prize catalogue; MNIST zeros: the 5,923 zeros of the
# training split at 28x28. The metric comes from the dataset registry.
FAMILIES = {
    "rnaseq20k_like": (20000, 27998),
    "netflix20k_like": (20000, 17770),
    "mnist_zeros_like": (5923, 784),
}
BUDGET_PER_ARM = 30
SERVE = {"requests": 16, "n_lo": 1000, "n_hi": 8000, "d": 784,
         "max_batch": 8}
CORPUS = {"n": 1000, "d": 784, "inserts": 40, "deletes": 8}
KMEDOIDS = {"n": 4096, "d": 128, "k": 8, "dataset": "rnaseq_like",
            "min_ari": 0.95}
SHARDED = ("netflix20k_like", "rnaseq20k_like")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds jax spent in XLA backend compiles (persistent-cache reads
    included), summed from jax's own monitoring events."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == _BACKEND_COMPILE:
            self.total += duration


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


class Smoke:
    """Runs phases in order; each prints one JSON line. A failed phase is
    reported (traceback on stderr) and makes the run fail at the end."""

    def __init__(self):
        self.clock = CompileClock()
        self.answers: dict[str, dict] = {}
        self.failed: list[str] = []

    def phase(self, name: str, fn, *args) -> None:
        c0, t0 = self.clock.total, time.perf_counter()
        try:
            answer, ok = fn(*args), True
            self.answers[name] = answer
        except Exception as e:  # noqa: BLE001 — reported, and fails the run
            traceback.print_exc()
            answer, ok = {"error": f"{type(e).__name__}: {e}"}, False
            self.failed.append(name)
        print(json.dumps({"phase": name, "ok": ok,
                          "wall_s": time.perf_counter() - t0,
                          "compile_s": self.clock.total - c0,
                          "answer": answer}), flush=True)


# ------------------------------- one chip -----------------------------------

def family_fused(name: str) -> dict:
    from repro.launch import medoid as medoid_cli

    n, d = FAMILIES[name]
    out = medoid_cli.run(n, d, "", BUDGET_PER_ARM, name,
                         backend="pallas_fused", compare=True)
    check(out["platform"] == "tpu", f"ran on {out['platform']}")
    check(out["correct"], f"corrSH medoid {out['medoid']} != exact "
                          f"{out['exact']}")
    return {k: out[k] for k in ("n", "d", "metric", "medoid", "exact",
                                "correct", "corrsh_s", "exact_s",
                                "pulls_scheduled")}


def family_reference(name: str, fused: dict | None) -> dict:
    from repro.launch import medoid as medoid_cli

    n, d = FAMILIES[name]
    out = medoid_cli.run(n, d, "", BUDGET_PER_ARM, name,
                         backend="reference")
    check(fused is not None, "no pallas_fused answer to compare with")
    check(out["medoid"] == fused["medoid"],
          f"reference medoid {out['medoid']} != pallas_fused "
          f"{fused['medoid']}")
    return {"medoid": out["medoid"], "pallas_fused_medoid": fused["medoid"],
            "corrsh_s": out["corrsh_s"]}


def custom_calls() -> dict:
    from repro.data.medoid_datasets import DATASETS
    from repro.engine import programs

    found = {}
    for name, (n, d) in FAMILIES.items():
        fn = programs.medoid_program(budget=BUDGET_PER_ARM * n,
                                     metric=DATASETS[name][0],
                                     backend="pallas_fused")
        text = fn.lower(jax.ShapeDtypeStruct((n, d), jnp.float32),
                        jax.random.key(0)).as_text()
        found[name] = text.count("tpu_custom_call")
    check(all(found.values()), f"no tpu_custom_call in {found}")
    return {"tpu_custom_call": found}


def precision_bf16(fp32: dict | None) -> dict:
    from repro.api import find_medoid
    from repro.data.medoid_datasets import DATASETS

    name = "netflix20k_like"
    n, d = FAMILIES[name]
    metric, gen = DATASETS[name]
    key = jax.random.key(0)        # the draws launch.medoid.run makes
    res = find_medoid(gen(key, n, d), jax.random.fold_in(key, 1),
                      metric=metric, backend="pallas_fused",
                      budget_per_arm=BUDGET_PER_ARM, precision="bf16")
    check(fp32 is not None, "no fp32 answer to compare with")
    check(res.verified is True, f"bf16 run not verified: {res.verified}")
    check(res.medoid == fp32["medoid"],
          f"bf16 medoid {res.medoid} != fp32 {fp32['medoid']}")
    return {"medoid": res.medoid, "verified": res.verified,
            "fp32_medoid": fp32["medoid"], "pulls": res.pulls}


def _mnist_queries(count: int, n_lo: int, n_hi: int, d: int, seed: int):
    """Log-uniform query sizes in [n_lo, n_hi], MNIST-zeros-like rows."""
    from repro.data.medoid_datasets import mnist_zeros_like

    key = jax.random.key(seed)
    u = jax.random.uniform(jax.random.fold_in(key, 0), (count,))
    sizes = [int(round(math.exp(math.log(n_lo)
                                + float(v) * (math.log(n_hi) - math.log(n_lo)))))
             for v in u]
    return [mnist_zeros_like(jax.random.fold_in(key, 1 + i), s, d)
            for i, s in enumerate(sizes)]


def serving() -> dict:
    from repro.core import exact_medoid
    from repro.launch.serve_medoid import MedoidServer

    qs = _mnist_queries(SERVE["requests"], SERVE["n_lo"], SERVE["n_hi"],
                        SERVE["d"], seed=1)
    srv = MedoidServer(metric="l2", backend="pallas_fused_topk",
                       max_batch=SERVE["max_batch"])
    warm = srv.warmup(sorted({(q.shape[0], q.shape[1]) for q in qs}))
    t0 = time.perf_counter()
    rids = [srv.submit(q) for q in qs]
    srv.drain()
    serve_s = time.perf_counter() - t0
    got = [srv.done[r].medoid for r in rids]
    want = [int(exact_medoid(q, "l2")) for q in qs]
    stats = srv.stats()
    check(stats["recompiles"] == 0, f"{stats['recompiles']} recompiles")
    check(got == want, f"served {got} != exact {want}")
    return {"sizes": [int(q.shape[0]) for q in qs], "medoids": got,
            "recompiles": stats["recompiles"],
            "dispatches": stats["dispatches"],
            "buckets": sorted(warm["buckets"]),
            "warmup_s": warm["wall_s"], "serve_s": serve_s}


def corpus() -> dict:
    import numpy as np

    from repro.api import maintain_medoid
    from repro.core import exact_medoid
    from repro.data.medoid_datasets import mnist_zeros_like

    n, d = CORPUS["n"], CORPUS["d"]
    pts = mnist_zeros_like(jax.random.key(2), n + CORPUS["inserts"], d)
    live = maintain_medoid(pts[:n], metric="l2", backend="pallas_fused")
    cap0 = live.store.capacity
    for i in range(CORPUS["inserts"]):
        live.insert(pts[n + i])
    victims = [live.query()[0]] + list(range(CORPUS["deletes"] - 1))
    for slot in victims:
        if live.store.is_live(slot):
            live.delete(slot)
    medoid, version = live.query()
    slots = live.store.live_slots()
    exact = int(slots[int(exact_medoid(jnp.asarray(live.store.snapshot()),
                                       "l2"))])
    stats = live.stats()
    check(stats["grows"] >= 1, "capacity never doubled")
    check(medoid == exact, f"maintained medoid {medoid} != exact {exact}")
    return {"medoid": medoid, "exact": exact, "version": version,
            "n": int(np.size(slots)), "capacity": [cap0, stats["capacity"]],
            "reruns": stats["reruns"], "kept": stats["kept"]}


def kmedoids_fit() -> dict:
    from repro.launch import kmedoids as kmedoids_cli

    out = kmedoids_cli.run(KMEDOIDS["n"], KMEDOIDS["d"], KMEDOIDS["k"],
                           KMEDOIDS["dataset"], backend="pallas_fused")
    check(out["ari"] >= KMEDOIDS["min_ari"], f"ARI {out['ari']} < "
                                             f"{KMEDOIDS['min_ari']}")
    return {k: out[k] for k in ("medoids", "ari", "cost", "pulls",
                                "swaps", "wall_s")}


def one_chip(smoke: Smoke) -> None:
    for name in FAMILIES:
        smoke.phase(f"{name}:pallas_fused", family_fused, name)
        smoke.phase(f"{name}:reference", family_reference, name,
                    smoke.answers.get(f"{name}:pallas_fused"))
    smoke.phase("tpu_custom_call", custom_calls)
    smoke.phase("precision_bf16", precision_bf16,
                smoke.answers.get("netflix20k_like:pallas_fused"))
    smoke.phase("serving", serving)
    smoke.phase("corpus", corpus)
    smoke.phase("kmedoids", kmedoids_fit)


# ------------------------------- four chips ---------------------------------

def sharded(name: str) -> dict:
    from repro.api import find_medoid
    from repro.core import exact_medoid
    from repro.core.distributed import (make_distributed_corr_sh,
                                        make_row_sharding)
    from repro.core.distributed_v2 import make_distributed_corr_sh_v2
    from repro.data.medoid_datasets import DATASETS

    n, d = FAMILIES[name]
    metric, gen = DATASETS[name]
    key = jax.random.key(0)
    data = gen(key, n, d)
    exact = int(exact_medoid(data, metric))
    mesh = jax.make_mesh((4,), ("data",))
    xs = jax.device_put(data, make_row_sharding(mesh))
    del data
    rows = sorted(int(s.data.shape[0]) for s in xs.addressable_shards)
    check(rows == [n // 4] * 4, f"shard rows {rows}")
    qkey = jax.random.fold_in(key, 1)
    out = {"exact": exact, "shard_rows": rows}
    for impl, make in (("v2", make_distributed_corr_sh_v2),
                       ("v1", make_distributed_corr_sh)):
        prog = make(mesh, n=n, d=d, budget=BUDGET_PER_ARM * n,
                    metric=metric, backend="pallas_fused")
        text = prog.lower(xs, qkey).compile().as_text()
        res = find_medoid(xs, qkey, mesh=mesh, distributed_impl=impl,
                          metric=metric, backend="pallas_fused",
                          budget_per_arm=BUDGET_PER_ARM)
        out[impl] = {"medoid": res.medoid,
                     "all_reduce": text.count("all-reduce"),
                     "tpu_custom_call": text.count("tpu_custom_call")}
        check("all-reduce" in text, f"{impl}: no all-reduce compiled")
        check(res.medoid == exact, f"{impl}: medoid {res.medoid} != exact "
                                   f"{exact}")
    return out


def four_chips(smoke: Smoke) -> None:
    for name in SHARDED:
        smoke.phase(f"sharded:{name}", sharded, name)


# --------------------------------- main -------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip phases; 4: only the sharded path")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.engine.programs import enable_compile_cache

    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    smoke = Smoke()
    (four_chips if args.chips == 4 else one_chip)(smoke)
    if smoke.failed:
        print(f"chip_smoke: failed phases: {smoke.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
