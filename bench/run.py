#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, from the root of a checkout:

  python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json`` with its generator under
``bench/generators/``) under a traffic mix (``bench/traffic/<traffic>.json``)
that names the entry point it drives (``bench/entries/<entry>.py``). The
run sets up (data drawn on the device from the seed, every program the
traffic uses compiled or read from the compile cache, warm-up), measures
for ``--seconds``, then checks every answer of the window against the
plain reference (``bench/reference.py``) and prints one JSON line last on
standard output. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, read by ``bench/metrics/<name>.py``
from a profiler trace of the window and the program's counters.

Exits 3 and prints no result where JAX finds no TPU, or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".jax_cache")
OUT_DIR = os.path.join(BENCH, ".out")
NO_CHIP = 3


def load(path: str):
    """Import one file of the benchmark by its path (names may hold dots)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_") \
        .replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def resolve(spec: dict, workload: str) -> dict:
    """Every file a cell needs, found by the names in ``BENCHMARK.json``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; one of "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = read_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = read_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    e2e = [m for m in spec["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (cell["name"] in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return {
        "cell": cell, "config": config, "traffic": traffic,
        "generator": os.path.join(BENCH, "generators",
                                  config["generator"] + ".py"),
        "entry": os.path.join(BENCH, "entries", traffic["entry"] + ".py"),
        "end_to_end": e2e, "per_layer": layer,
        "readers": {m["name"]: os.path.join(BENCH, "metrics",
                                            m["name"] + ".py")
                    for m in e2e + layer},
    }


def seed_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


class CompileClock:
    """Seconds and events of XLA compiles and compile-cache reads, from
    jax's own monitoring events, split by phase of the run."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.phase = "setup"
        self.seconds = {}
        self.compiles = {}
        self.read_s = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.seconds[self.phase] = self.seconds.get(self.phase, 0.0) \
                + duration
        if event == self.EVENTS[0]:
            self.compiles[self.phase] = self.compiles.get(self.phase, 0) + 1
        elif event == self.EVENTS[1]:
            self.read_s[self.phase] = self.read_s.get(self.phase, 0.0) \
                + duration


def device_block(devices, chips: int) -> dict:
    used = devices[:chips]
    peak = 0
    for dev in used:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def find_chips(chips: int):
    """Point JAX's compile cache at ``CACHE_DIR`` and return its devices if
    they hold ``chips`` TPU chips; otherwise say why on stderr and return
    None. Nothing touches JAX before this."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no accelerator: {e}", file=sys.stderr)
        return None
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: {chips} TPU chip(s) needed; JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None
    return devices


def setup(files: dict, seed: int, spans):
    """The set-up of every run of a cell: the program's compile cache, the
    cell's entry built from its files (data drawn on the device from the
    seed) and warmed on every shape its traffic uses. Returns the entry."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from repro.engine.programs import enable_compile_cache

    enable_compile_cache()
    entry = load(files["entry"]).Entry(
        config=files["config"], traffic=files["traffic"],
        generator=load(files["generator"]), key=seed_key(seed), spans=spans)
    entry.warm()
    return entry


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    files = resolve(spec, args.workload)
    devices = find_chips(files["cell"]["chips"])
    if devices is None:
        return NO_CHIP
    return measure(args, files, devices)


def measure(args, files, devices) -> int:
    import jax
    import numpy as np

    sys.path.insert(0, ROOT)
    from bench import loadgen, trace_reduce
    from bench.spans import Spans

    clock = CompileClock()
    cell, traffic = files["cell"], files["traffic"]
    spans = Spans(bool(args.trace))
    rng = np.random.default_rng(args.seed)
    entry = setup(files, args.seed, spans)
    setup_s = time.perf_counter() - T_START

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(OUT_DIR,
                                 f"trace-{cell['name']}-{os.getpid()}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # host spans are TraceMe events
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    clock.phase = "window"
    counters0 = entry.counters()
    if traffic["loop"] == "closed":
        run = loadgen.closed_loop(entry, args.seconds, spans)
    else:
        run = loadgen.open_loop(entry, traffic, args.seconds, rng, spans)
    counters1 = entry.counters()
    if args.trace:
        jax.profiler.stop_trace()
    clock.phase = "after"
    if traffic["loop"] == "open":
        loadgen.finish_open(entry, run)

    device = device_block(devices, cell["chips"])
    entry.release()
    with spans("reference"):
        checks = entry.check(run)

    summary = None
    if args.trace:
        summary = trace_reduce.summarize(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    ctx = types.SimpleNamespace(
        run=run, setup_s=setup_s, clock=clock, trace=summary,
        counters=(counters0, counters1), entry=entry, config=files["config"],
        traffic=traffic, device_kind=devices[0].device_kind)
    wanted = files["per_layer"] if args.trace else files["end_to_end"]
    metrics = {}
    for m in wanted:
        value = load(files["readers"][m["name"]]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    records = run["records"]
    failed = sum(r["answer"] is None for r in records)
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    out = {"correct": correct, "attempted": len(records), "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = summary.breakdown()
    out["checks"] = checks
    print(f"bench: {cell['name']} seed {args.seed}: setup {setup_s:.3f} s, "
          f"window {run['window_s']:.3f} s, {len(records)} requests, "
          f"generator late by at most {run['late_s'] * 1e3:.3f} ms, "
          f"compiles in set-up {clock.compiles.get('setup', 0)} "
          f"({clock.seconds.get('setup', 0.0):.3f} s, of which cache reads "
          f"{clock.read_s.get('setup', 0.0):.3f} s), "
          f"compiles in the window {clock.compiles.get('window', 0)}",
          file=sys.stderr)
    if "longest_step" in run:
        step_s, at_s = run["longest_step"]
        print(f"bench: longest step {step_s * 1e3:.3f} ms, {at_s:.3f} s "
              f"into the window", file=sys.stderr)
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
