"""The Netflix 20k cosine cell: the benchmark's copy of the ratings
generator, the plain reference under cosine on its rows, the cell's work
readers on the full-size schedule, and a tiny traced run of the cell."""
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, reference
from bench import run as harness
from bench.generators import netflix_like

CELL = "netflix20k_cosine.pipeline"


def reader(name):
    return harness.load(f"{harness.BENCH}/metrics/{name}.py").read


def test_the_configuration_names_its_own_source():
    # a deployment of the paper's is told apart by the part that defines
    # it, not by the paper alone
    spec = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entries = {c["name"]: c for c in spec["configs"]}
    ours = entries.pop("netflix20k_cosine")
    assert "Netflix 20k" in ours["source"] and len(ours["source"]) <= 200
    assert all(c["source"] != ours["source"] for c in entries.values())


def test_generator_is_deterministic_per_seed():
    a = netflix_like.generate(jax.random.key(7), (33, 20), 40)
    b = netflix_like.generate(jax.random.key(7), (33, 20), 40)
    c = netflix_like.generate(jax.random.key(8), (33, 20), 40)
    assert [s.shape for s in a] == [(33, 40), (20, 40)]
    for u, v, w in zip(a, b, c):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
        assert not np.array_equal(np.asarray(u), np.asarray(w))


def test_generator_is_the_repos_netflix_like():
    from repro.data.medoid_datasets import netflix_like as repo

    key = jax.random.key(11)
    ours = netflix_like.generate(key, (50,), 64)[0]
    ours, theirs = np.asarray(ours), np.asarray(repo(jax.random.fold_in(
        key, 0), 50, 64))
    # the same draws; one jitted call rounds the arithmetic in its own
    # order, so values may differ in the last bit, never the ratings held
    np.testing.assert_array_equal(ours > 0, theirs > 0)
    np.testing.assert_allclose(ours, theirs, rtol=1e-6, atol=1e-7)


def test_density_at_the_catalogue_width():
    x = np.asarray(netflix_like.generate(jax.random.key(7), (300,),
                                         17770)[0])
    assert np.all(x >= 0) and np.all(x[:, 0] >= 1e-3)
    density = float((x > 0).mean())
    assert 0.003 < density < 0.01, density


def brute_cosine_theta(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.float64)
    norms = np.linalg.norm(x, axis=1)
    return (1.0 - (x @ x.T) / np.outer(norms, norms)).mean(1)


@pytest.mark.parametrize("seed", [3, 4])
def test_reference_cosine_matches_brute_force(seed):
    x = netflix_like.generate(jax.random.key(seed), (300,), 300)[0]
    want = brute_cosine_theta(np.asarray(x))
    got = np.asarray(reference.centrality(x, jnp.int32(300), metric="cosine",
                                          block=64))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)
    assert reference.medoid(x, "cosine")[0] == int(np.argmin(want))


# Netflix 20k at 30 pulls per arm: scan bands of width 20000, 2500, 313, 40
# and 5 against reference buffers of 8, 64, 506, 4000 and 13333 (3, 3, 3, 3
# and 2 trips), then 2 arms x 20000 references; Gram tiles 128 x 128 x 256.
BLOCKS = [(20000, 8, 3), (2500, 64, 3), (313, 506, 3), (40, 4000, 3),
          (5, 13333, 2), (2, 20000, 1)]
N, D = 20000, 17770


def pad(v, b):
    return -(-v // b) * b


CALLED = sum(r * t * k for r, t, k in BLOCKS) * D
COMPUTED = sum(pad(r, 128) * pad(t, 128) * k for r, t, k in BLOCKS) \
    * pad(D, 256)
NORMED = sum(k * (r + t) for r, t, k in BLOCKS)


def netflix_ctx():
    return types.SimpleNamespace(config={"n": N}, entry=types.SimpleNamespace(
        work=counts.work(N, D, 30 * N)))


def test_tile_work_and_norm_rows_of_netflix20k(monkeypatch):
    from repro.engine import instrument

    monkeypatch.setattr(instrument, "work_counters", lambda: {
        "called": {"medoid": 28 * CALLED},
        "computed": {"medoid": 28 * COMPUTED},
        "normed": {"medoid": 28 * NORMED}})
    monkeypatch.setattr(instrument, "dispatch_count", lambda kind=None: 28)
    assert NORMED == 128971
    assert reader("norm_rows.netflix")(netflix_ctx()) == pytest.approx(
        6.44855)
    assert reader("tile_work.pipeline")(netflix_ctx()) == pytest.approx(
        8.148558, rel=1e-6)
    # the same bands as the l1 pipeline's: n and the budget fix them
    assert reader("scan_work.pipeline")(netflix_ctx()) == pytest.approx(
        3.4831, rel=1e-4)


def test_norm_rows_reads_nothing_without_the_tally(monkeypatch):
    from repro.engine import instrument

    # an odometer that predates the ``normed`` tally
    monkeypatch.setattr(instrument, "work_counters", lambda: {
        "called": {"medoid": CALLED}, "computed": {"medoid": COMPUTED}})
    monkeypatch.setattr(instrument, "dispatch_count", lambda kind=None: 1)
    assert reader("norm_rows.netflix")(netflix_ctx()) is None
    monkeypatch.delattr(instrument, "work_counters")
    assert reader("norm_rows.netflix")(netflix_ctx()) is None
    assert reader("tile_work.pipeline")(netflix_ctx()) is None


def test_device_readers_read_nothing_without_a_trace():
    ctx = types.SimpleNamespace(trace=None)
    for name in ("cosine_centrality.roofline", "glue_ms",
                 "idle_share.pipeline"):
        assert reader(name)(ctx) is None, name


def run_tiny(capsys, *, trace=0, seed=2 ** 31 + 29):
    """One run of the cell at n = 256 on the CPU, as ``measure`` runs it
    once ``main`` has found the chip."""
    files = harness.resolve(harness.read_json(os.path.join(
        harness.ROOT, "BENCHMARK.json")), CELL)
    files["config"] = dict(files["config"], n=256, d=64)
    args = types.SimpleNamespace(seed=seed, seconds=1.0, trace=trace)
    assert harness.measure(args, files, jax.devices()) == 0
    cap = capsys.readouterr()
    return json.loads(cap.out.strip().splitlines()[-1]), cap.err


def test_traced_run_of_the_cell(capsys, monkeypatch):
    from bench import peaks
    from repro.engine import instrument

    # the roofline reader needs a device's peaks: lend the CPU the chip's
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    # the work readers take process totals, as a benchmark process holds
    # only the cell's dispatches; show them this run's alone
    since = instrument.deltas().__enter__()
    monkeypatch.setattr(instrument, "work_counters", lambda: {
        tally: {"medoid": getattr(since.work("medoid"), tally)}
        for tally in ("called", "computed", "normed")})
    monkeypatch.setattr(instrument, "dispatch_count", since.dispatch)
    out, err = run_tiny(capsys, trace=1)
    assert out["correct"] is True, err
    assert set(out["checks"]) == {"miss_share"}
    metrics = out["metrics"]
    assert metrics["tile_work.pipeline"]["value"] > 1.0
    # n = 256 at 30 pulls per arm: bands 256 x 15, 32 x 120 (3 trips
    # each) and 4 x 240 (1 trip), then 2 arms x 256 references
    assert metrics["norm_rows.netflix"]["value"] == pytest.approx(
        (3 * 271 + 3 * 152 + 244 + 258) / 256)
