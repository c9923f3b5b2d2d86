"""The per-layer metrics that read the program's own counters: queue wait
from the server's histogram, scan and tile work from the work odometer.
Each reads a fixture context, reads nothing where the program keeps no
such counter, and finds its counter in a traced run of its cell."""
import types

import jax
import pytest

from bench import counts
from bench import run as harness
from bench.tests import cpu_run


def reader(name):
    return harness.load(f"{harness.BENCH}/metrics/{name}.py").read


def snapshot(family, total, count):
    series = [{"labels": {"bucket": "8192x784"}, "sum": total,
               "count": count}]
    return {"metrics": {family: {"series": series}}}


def test_queue_wait_is_the_window_mean_in_ms():
    ctx = types.SimpleNamespace(counters=(
        snapshot("medoid_queue_wait_seconds", 1.0, 10),
        snapshot("medoid_queue_wait_seconds", 5.5, 100)))
    assert reader("queue_wait_ms.serve")(ctx) == pytest.approx(50.0)


def test_queue_wait_reads_nothing_without_the_histogram():
    ctx = types.SimpleNamespace(counters=(
        snapshot("medoid_queue_wait_steps", 1.0, 10),
        snapshot("medoid_queue_wait_steps", 5.5, 100)))
    assert reader("queue_wait_ms.serve")(ctx) is None


# RNA-Seq 20k at 30 pulls per arm: scan bands of width 20000, 2500, 313,
# 40 and 5 against reference buffers of 8, 64, 506, 4000 and 13333 (3, 3,
# 3, 3 and 2 trips), then 2 arms x 20000 references; tiles 128 x 128 x 256.
BLOCKS = [(20000, 8, 3), (2500, 64, 3), (313, 506, 3), (40, 4000, 3),
          (5, 13333, 2), (2, 20000, 1)]
D = 27998


def pad(v, b):
    return -(-v // b) * b


CALLED = sum(r * t * k for r, t, k in BLOCKS) * D
COMPUTED = sum(pad(r, 128) * pad(t, 128) * k for r, t, k in BLOCKS) \
    * pad(D, 256)


@pytest.fixture
def odometer(monkeypatch):
    from repro.engine import instrument

    def plant(dispatches, called, computed):
        monkeypatch.setattr(instrument, "work_counters", lambda: {
            "called": {"medoid": called}, "computed": {"medoid": computed}})
        monkeypatch.setattr(instrument, "dispatch_count",
                            lambda kind=None: dispatches)
    return plant


def pipeline_ctx():
    return types.SimpleNamespace(entry=types.SimpleNamespace(
        work=counts.work(20000, D, 30 * 20000)))


def test_scan_and_tile_work_of_rnaseq20k(odometer):
    odometer(28, 28 * CALLED, 28 * COMPUTED)
    ctx = pipeline_ctx()
    assert ctx.entry.work["pulls"] == 599602
    scan = reader("scan_work.pipeline")(ctx)
    tile = reader("tile_work.pipeline")(ctx)
    assert scan == pytest.approx(CALLED / ctx.entry.work["terms"])
    assert tile == pytest.approx(COMPUTED / CALLED)
    assert 3.4 < scan < 3.6 and 8.0 < tile < 8.3


def test_work_metrics_read_nothing_without_the_odometer(monkeypatch):
    from repro.engine import instrument

    monkeypatch.delattr(instrument, "work_counters")
    ctx = pipeline_ctx()
    assert reader("scan_work.pipeline")(ctx) is None
    assert reader("tile_work.pipeline")(ctx) is None


@pytest.mark.parametrize("cell,names", [
    ("rnaseq20k_l1.pipeline", ("scan_work.pipeline", "tile_work.pipeline")),
    ("mnist_zeros_l2.serve", ("queue_wait_ms.serve",))])
def test_traced_run_reports_the_program_metrics(cell, names, capsys,
                                                monkeypatch):
    from bench import peaks

    # the cell's roofline readers need a device's peaks: lend the CPU the
    # chip's, as nothing here reads a roofline
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    out, _ = cpu_run.run(cell, capsys, trace=1, seed=2 ** 31 + 17)
    for name in names:
        assert out["metrics"][name]["value"] > 0, name
