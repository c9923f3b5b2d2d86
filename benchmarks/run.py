"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (the scaffold contract), one
section per benchmark, and writes each section's rows as machine-readable
``BENCH_<section>.json`` (``--out-dir``, default cwd) so the perf trajectory
is tracked across PRs. Scale knobs are CI-sized; pass --full for paper-scale.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _normalize(r: dict) -> dict:
    """One canonical row: name, us_per_call, optional pulls, derived."""
    name = r.get("name") or "_".join(
        str(r.get(k)) for k in ("dataset", "algo", "arm", "pulls_per_arm")
        if r.get(k) is not None)
    # NB: `sec == 0.0` is a legitimate value — test membership, never truth
    # (`r.get("sec", 0) and ...` used to short-circuit to 0 and print an
    # empty/zero us_per_call for instant calls).
    if "us_per_call" in r:
        us = r["us_per_call"]
    elif "sec" in r:
        us = r["sec"] * 1e6
    else:
        us = ""
    derived = r.get("derived") or json.dumps(
        {k: v for k, v in r.items()
         if k not in ("name", "us_per_call", "sec", "dataset", "algo")})
    out = {"name": name, "us_per_call": us, "derived": derived}
    if "pulls" in r:
        out["pulls"] = r["pulls"]
    # first-call (trace+compile) vs steady-state split, where a section
    # reports it — us_per_call alone conflates one-time compilation with
    # the recurring serving cost the one-program engine optimizes for
    for key in ("compile_us", "steady_us", "counters", "telemetry"):
        if key in r:
            out[key] = r[key]
    return out


def _emit(rows):
    normalized = [_normalize(r) for r in rows]
    for r in normalized:
        print(f"{r['name']},{r['us_per_call']},{r['derived']!r}")
        sys.stdout.flush()
    return normalized


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--only", default=None,
                    choices=[None, "algorithms", "curves", "correlation",
                             "kernels", "backends", "ragged", "cluster",
                             "engine", "serve", "quant", "roofline"])
    ap.add_argument("--out-dir", default=".",
                    help="where BENCH_<section>.json files are written")
    args = ap.parse_args()
    scale = 2 if args.full else 1

    from repro.engine.programs import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_algorithms, bench_backends, bench_cluster,
                            bench_correlation, bench_engine,
                            bench_error_curves, bench_kernels, bench_quant,
                            bench_ragged, bench_serve, roofline_table)

    sections = {
        "algorithms": lambda: bench_algorithms.run(
            n=2048 * scale, d=256 * scale, trials=10 * scale),
        "curves": lambda: bench_error_curves.run(
            n=1024 * scale, d=128 * scale, trials=20 * scale),
        "correlation": lambda: bench_correlation.run(
            n=1024 * scale, d=256 * scale),
        "kernels": lambda: bench_kernels.run(),
        "backends": lambda: bench_backends.run(
            grid=((512 * scale, 64 * scale), (1024 * scale, 128 * scale))),
        "ragged": lambda: bench_ragged.run(
            ns=(64, 257, 1024), d=16 * scale),
        "cluster": lambda: bench_cluster.run(
            n_small=512, n_big=4096, d=64 * scale),
        "engine": lambda: bench_engine.run(d=16 * scale),
        "serve": lambda: bench_serve.run(steps=120 * scale),
        "quant": lambda: bench_quant.run(n=1024, d=16 * scale),
        "roofline": lambda: roofline_table.run(
            ("results_dryrun_16x16.jsonl", "results_dryrun_2x16x16.jsonl")),
    }
    for name, fn in sections.items():
        if args.only and name != args.only:
            continue
        print(f"# === {name} ===")
        t0 = time.time()
        rows = _emit(fn())
        path = os.path.join(args.out_dir, f"BENCH_{name}.json")
        with open(path, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"# {name} done in {time.time() - t0:.1f}s "
              f"({path})", file=sys.stderr)


if __name__ == "__main__":
    main()
