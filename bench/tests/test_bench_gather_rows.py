"""gather_rows.pipeline: the rows the round loop copies out of the corpus
per query, over n. The counts are the program's own, taken at trace time
on each pipeline's full-size schedule; the reader reads 0 where every call
fetches its rows by index, and nothing where the odometer keeps no such
tally."""
import types

import jax
import jax.numpy as jnp
import pytest

from bench import run as harness
from bench.tests import cpu_run

N = 20000


def reader():
    return harness.load(f"{harness.BENCH}/metrics/gather_rows.pipeline.py"
                        ).read


def traced_rows(metric: str, d: int) -> int:
    """Rows one query of the pipeline's program gathers: its round loop
    traced (never run) at the full shape, as ``medoid_program`` builds it."""
    from repro.engine import instrument
    from repro.engine.estimators import medoid_centrality
    from repro.engine.halving import HalvingProblem, run_halving
    from repro.engine.schedule import round_schedule

    est = medoid_centrality("pallas_fused", metric)
    with instrument.tally() as work:
        jax.eval_shape(lambda x, k: run_halving(
            HalvingProblem(x, est), round_schedule(N, 30 * N),
            "pallas_fused", key=k).winner,
            jax.ShapeDtypeStruct((N, d), jnp.float32),
            jax.eval_shape(lambda: jax.random.key(0)))
    return work.gathered


def plant(monkeypatch, gathered, dispatches=28):
    from repro.engine import instrument

    monkeypatch.setattr(instrument, "work_counters", lambda: {
        "called": {"medoid": 1}, "computed": {"medoid": 1},
        "normed": {}, "gathered": {"medoid": dispatches * gathered}})
    monkeypatch.setattr(instrument, "dispatch_count",
                        lambda kind=None: dispatches)


@pytest.mark.parametrize("metric,d,rows,value", [
    ("l1", 27998, 0, 0.0),             # rnaseq20k_l1: by index
    ("cosine", 17770, 128971, 6.44855)])  # netflix20k_cosine: gathered
def test_gather_rows_of_the_pipelines(monkeypatch, metric, d, rows, value):
    got = traced_rows(metric, d)
    assert got == rows
    plant(monkeypatch, got)
    read = reader()(types.SimpleNamespace(config={"n": N}))
    assert read == pytest.approx(value, rel=1e-6)
    assert (read == 0.0) is (rows == 0)


def test_gather_rows_reads_nothing_without_the_tally(monkeypatch):
    from repro.engine import instrument

    ctx = types.SimpleNamespace(config={"n": N})
    # an odometer that predates the ``gathered`` tally (the parent's)
    monkeypatch.setattr(instrument, "work_counters", lambda: {
        "called": {"medoid": 1}, "computed": {"medoid": 1},
        "normed": {}})
    monkeypatch.setattr(instrument, "dispatch_count", lambda kind=None: 3)
    assert reader()(ctx) is None
    plant(monkeypatch, 0, dispatches=0)        # no query ran
    assert reader()(ctx) is None
    monkeypatch.delattr(instrument, "work_counters")
    assert reader()(ctx) is None


def test_traced_run_of_the_l1_cell_reads_zero(capsys, monkeypatch):
    from bench import peaks
    from repro.engine import instrument

    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    # the reader takes process totals, as a benchmark process holds only
    # the cell's dispatches; show it this run's alone
    since = instrument.deltas().__enter__()
    monkeypatch.setattr(instrument, "work_counters", lambda: {
        tally: {"medoid": getattr(since.work("medoid"), tally)}
        for tally in ("called", "computed", "normed", "gathered")})
    monkeypatch.setattr(instrument, "dispatch_count", since.dispatch)
    out, err = cpu_run.run("rnaseq20k_l1.pipeline", capsys, trace=1,
                           seed=2 ** 31 + 41)
    assert out["correct"] is True, err
    assert out["metrics"]["gather_rows.pipeline"]["value"] == 0.0
