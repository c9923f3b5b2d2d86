"""The harness: files found by name, the result line, and no run without a
chip. Runs that need the program drive ``measure`` on the CPU at a tiny
size (it is what ``main`` calls once it has found the chip)."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run as harness
from bench.tests import cpu_run

ROOT = harness.ROOT


def spec():
    return harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("cell", [w["name"] for w in spec()["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    files = harness.resolve(spec(), cell)
    for key in ("generator", "entry"):
        assert os.path.isfile(files[key]), files[key]
    assert files["readers"] and all(os.path.isfile(p)
                                    for p in files["readers"].values())
    names = {m["name"] for m in files["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert files["per_layer"]
    for m in files["per_layer"]:
        assert m["moves"] in names


def test_config_files_state_what_they_run():
    for c in spec()["configs"]:
        cfg = harness.read_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        for key in ("source", "metric", "d", "generator", "guarantees",
                    "assumed", "correct"):
            assert key in cfg, key


def digest(root):
    out = {}
    for base, _, names in os.walk(root):
        for n in names:
            p = os.path.join(base, n)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_config_and_cell_are_found_from_new_files_alone(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".out"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = digest(tmp_path / "bench")
    new = {
        "configs/tiny_cosine.json": json.dumps(
            {"name": "tiny_cosine", "generator": "tiny_gen"}),
        "generators/tiny_gen.py": "def generate(key, sizes, d):\n    pass\n",
        "traffic/burst.json": json.dumps({"loop": "closed",
                                          "entry": "tiny_entry"}),
        "entries/tiny_entry.py": "class Entry:\n    pass\n",
        "metrics/tiny_ms.py": "def read(ctx):\n    return 1.0\n",
        "metrics/tiny_layer.py": "def read(ctx):\n    return None\n",
    }
    for rel, text in new.items():
        (tmp_path / "bench" / rel).write_text(text)
    s = json.loads((tmp_path / "BENCHMARK.json").read_text())
    s["configs"].append({"name": "tiny_cosine", "source": "x",
                         "file": "bench/configs/tiny_cosine.json",
                         "reduced": [], "why": "x"})
    s["workloads"].append({"name": "tiny_cosine.burst",
                           "config": "tiny_cosine", "traffic": "burst",
                           "chips": 1, "why": "x"})
    s["end_to_end"].append({"name": "tiny_ms", "unit": "ms",
                            "better": "lower", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["tiny_cosine.burst"]})
    s["per_layer"].append({"name": "tiny_layer", "unit": "%",
                           "better": "higher", "source": "device_trace",
                           "layer": "x", "moves": "tiny_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))
    copy = harness.load(str(tmp_path / "bench" / "run.py"))
    files = copy.resolve(s, "tiny_cosine.burst")
    assert files["config"]["name"] == "tiny_cosine"
    assert files["generator"] == str(tmp_path / "bench/generators/tiny_gen.py")
    assert files["entry"] == str(tmp_path / "bench/entries/tiny_entry.py")
    assert {m["name"] for m in files["end_to_end"]} == {"setup_s", "tiny_ms"}
    assert {m["name"] for m in files["per_layer"]} == {"compile_s",
                                                        "tiny_layer"}
    after = digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_no_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "rnaseq20k_l1.pipeline", "--seed", "3000000000",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == harness.NO_CHIP
    assert p.stdout == ""
    assert "1 TPU chip(s) needed" in p.stderr


def test_benchmark_files_alone_cannot_run(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no program:
    past the chip check the run fails and prints no result."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".out"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    script = ("import sys, types, jax\n"
              "sys.path.insert(0, 'bench')\n"
              "import run\n"
              "files = run.resolve(run.read_json('BENCHMARK.json'), "
              "'rnaseq20k_l1.pipeline')\n"
              "args = types.SimpleNamespace(seed=1, seconds=1.0, trace=0)\n"
              "sys.exit(run.measure(args, files, jax.devices()))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "repro" in p.stderr


@pytest.mark.parametrize("cell,trace", [("rnaseq20k_l1.pipeline", 0),
                                        ("mnist_zeros_l2.saturate", 1)])
def test_result_line_carries_the_contract_keys(cell, trace, capsys):
    out, err = cpu_run.run(cell, capsys, trace=trace, seed=2 ** 31 + 11)
    want = ["correct", "attempted", "failed", "metrics", "device"]
    if trace:
        want.append("breakdown")
    assert list(out) == want + ["checks"]
    assert isinstance(out["correct"], bool) and out["failed"] == 0
    assert out["attempted"] > 0
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        assert key in out["device"]
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert "setup_s" in out["metrics"]
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    lines = err.strip().splitlines()
    assert all(line.startswith("check ")
               for line in lines[-len(out["checks"]):])


def test_serving_pool_holds_sets_of_the_configured_n():
    """Every request of a serving cell is one set of the configuration's
    ``n`` rows; the traffic mix sets only how many sets the pool holds."""
    import jax

    from bench.spans import Spans

    files = cpu_run.files_for("mnist_zeros_l2.serve")
    files["config"] = dict(files["config"], n=40)
    files["traffic"] = dict(files["traffic"], pool=5)
    entry = harness.load(files["entry"]).Entry(
        config=files["config"], traffic=files["traffic"],
        generator=harness.load(files["generator"]),
        key=harness.seed_key(2 ** 31 + 5), spans=Spans(False))
    assert entry.pool_size == 5
    assert [s.shape for s in entry.sets] == [(40, files["config"]["d"])] * 5
    assert jax.numpy.unique(jax.numpy.stack(entry.sets)[:, 0, 0]).size == 5


@pytest.mark.parametrize("on", [False, True])
def test_spans_wrap_alike_in_traced_and_untraced_runs(on):
    """The program runs under the same Python frames with spans on or off:
    Pallas kernels carry those frames into the compile cache's key."""
    import inspect
    import types

    from bench.spans import Spans

    mod = types.SimpleNamespace(f=lambda x: (x, inspect.stack()[1].function))
    Spans(on).wrap(mod, "f", "f")
    assert mod.f(3) == (3, "spanned")
