"""Drive one run of a cell on the CPU at a tiny size, past the harness's
look for a chip, and return its result line and standard error."""
import json
import os
import types

import jax

from bench import run as harness

# tiny sizes per entry: what a test run can hold on the CPU
TINY_CONFIG = {"find_medoid": {"n": 256, "d": 64},
               "medoid_server": {"n": 150, "d": 32}}
TINY_TRAFFIC = {"find_medoid": {},
                "medoid_server": {"rate_per_s": 30, "pool": 12}}


def files_for(cell: str, **config_overrides) -> dict:
    spec = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    files = harness.resolve(spec, cell)
    entry = files["traffic"]["entry"]
    files["config"] = dict(files["config"], **TINY_CONFIG[entry],
                           **config_overrides)
    files["traffic"] = dict(files["traffic"], **TINY_TRAFFIC[entry])
    return files


def run(cell: str, capsys, *, trace: int = 0, seed: int = 1,
        seconds: float = 1.0, **config_overrides):
    files = files_for(cell, **config_overrides)
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
    assert harness.measure(args, files, jax.devices()) == 0
    cap = capsys.readouterr()
    return json.loads(cap.out.strip().splitlines()[-1]), cap.err
