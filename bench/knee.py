#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell to find its knee.

  python bench/knee.py --workload <open-loop cell> --seed <n> --seconds <s> \\
      --rates 100,200,400

Sets the cell up once, then offers each rate for ``--seconds`` in turn (the
traffic mix's own rate is ignored) and prints one JSON line per rate: the
requests due, answered in the window, the backlog at the close, the
median and 95th-percentile latency from due time, and how late the
generator ran. The knee is the highest rate whose backlog at the close is
no larger than at its start, give or take one batch, with the generator on
time. Exits 3 where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, req/s")
    args = ap.parse_args(argv)
    spec = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    files = harness.resolve(spec, args.workload)
    if files["traffic"]["loop"] != "open":
        ap.error(f"{args.workload} is not an open-loop cell")
    devices = harness.find_chips(files["cell"]["chips"])
    if devices is None:
        return harness.NO_CHIP
    import jax
    import numpy as np

    from bench import loadgen, stats
    from bench.spans import Spans

    entry = harness.setup(files, args.seed, Spans(False))
    rng = np.random.default_rng(args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(files["traffic"], rate_per_s=rate)
        run = loadgen.open_loop(entry, traffic, args.seconds, rng,
                                Spans(False))
        backlog = entry.pending + len(run["records"]) - run["submitted"]
        loadgen.finish_open(entry, run)
        lat = loadgen.latency_ms(run)
        served = sum(r["finish"] is not None and r["finish"] <= run["t_end"]
                     for r in run["records"])
        print(json.dumps({
            "rate_per_s": rate, "due": len(run["records"]),
            "served_per_s": served / args.seconds,
            "backlog_at_close": backlog,
            "p50_ms": stats.percentile(lat, 50),
            "p95_ms": stats.percentile(lat, 95),
            "late_ms": run["late_s"] * 1e3}), flush=True)
        jax.effects_barrier()
    print(json.dumps({"device": harness.device_block(devices, 1),
                      "workload": args.workload, "seed": args.seed,
                      "seconds": args.seconds}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
