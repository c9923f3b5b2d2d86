"""Observability subsystem: device telemetry, traces, serving metrics.

The PR-7 contract under test:

* **telemetry is free of observable effect** — with ``telemetry=True`` the
  winners, estimates, and pull counts are bitwise identical to
  ``telemetry=False``, on every backend, for the single / batched / ragged
  facade paths AND for the BUILD/SWAP estimators driven through
  ``run_halving`` directly (the stats are pure extra scan outputs over the
  same key sequence);
* **fixed shapes** — telemetry buffers are ``(R,)`` per query (``(B, R)``
  under the vmapped engines) with the schema of
  :data:`repro.obs.telemetry.FIELDS`, where R is the executed-round count —
  a static property of ``(n, budget)``;
* **exact accounting** — the per-round ``pulls`` column matches the round
  schedule row-for-row and sums to the facade's scheduled totals;
* **no new programs** — the telemetry variant compiles once per signature
  (like any program) and repeated calls trace nothing;
* **artifacts validate** — TraceSession JSONL streams and Prometheus
  expositions round-trip through :mod:`repro.obs.validate`, including the
  round-vs-select pull reconciliation and the +Inf-bucket == count
  histogram invariant.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import find_medoid, find_medoids_batch, find_medoids_ragged
from repro.core.backend import get_backend
from repro.engine import (HalvingProblem, build_delta, instrument,
                          round_schedule, run_halving, stop_round, swap_delta)
from repro.obs import (ServerMetrics, TraceSession, telemetry,
                       telemetry_to_host)
from repro.obs.validate import validate_exposition, validate_trace

pytestmark = pytest.mark.obs

BACKENDS = ("reference", "pallas_pairwise", "pallas_fused",
            "pallas_fused_topk")


def _executed(n: int, budget: int):
    rounds = round_schedule(n, budget)
    return rounds[: stop_round(rounds) + 1]


# --------------------------- bitwise answer parity ---------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_single_query_parity_and_accounting(backend):
    data = jax.random.normal(jax.random.key(0), (64, 5))
    kw = dict(budget_per_arm=17, backend=backend)
    off = find_medoid(data, jax.random.key(1), **kw)
    on = find_medoid(data, jax.random.key(1), telemetry=True, **kw)
    assert on.medoid == off.medoid
    assert on.pulls == off.pulls
    tel = on.telemetry
    executed = _executed(64, 17 * 64)
    assert set(tel) == set(telemetry.FIELDS)
    assert all(v.shape == (len(executed),) for v in tel.values())
    # schedule columns match the static plan row-for-row; measured columns
    # are finite where >= 2 arms were alive
    assert tel["pulls"].tolist() == [r.pulls for r in executed]
    assert tel["survivors"].tolist() == [r.survivors for r in executed]
    assert tel["num_refs"].tolist() == [r.num_refs for r in executed]
    assert int(tel["pulls"].sum()) == off.pulls
    assert tel["alive"].tolist()[0] == 64
    assert np.isfinite(tel["theta_med"]).all()
    assert float(tel["budget_frac"][-1]) == pytest.approx(1.0, abs=1e-5)


def test_batch_parity_and_vmap_shapes():
    data = jax.random.normal(jax.random.key(2), (3, 32, 4))
    off = np.asarray(find_medoids_batch(data, jax.random.key(3),
                                        budget_per_arm=11))
    on, tel = find_medoids_batch(data, jax.random.key(3), budget_per_arm=11,
                                 telemetry=True)
    assert np.array_equal(off, np.asarray(on))
    r = len(_executed(32, 11 * 32))
    assert all(v.shape == (3, r) for v in tel.values())
    # schedule columns broadcast across the batch; every query pays them
    assert np.array_equal(tel["pulls"][0], tel["pulls"][2])
    assert (tel["pulls"].sum(axis=1) == sum(
        x.pulls for x in _executed(32, 11 * 32))).all()


def test_ragged_parity_and_alive_column():
    qs = [jax.random.normal(jax.random.fold_in(jax.random.key(4), i), (n, 4))
          for i, n in enumerate((7, 21, 64))]     # all bucket to 64
    off = np.asarray(find_medoids_ragged(qs, key=jax.random.key(5),
                                         budget_per_arm=13))
    on, tel = find_medoids_ragged(qs, key=jax.random.key(5),
                                  budget_per_arm=13, telemetry=True)
    assert np.array_equal(off, np.asarray(on))
    # round 0's alive count is each query's true length — padding is
    # masked out of the telemetry exactly as it is out of the estimates
    assert tel["alive"][:, 0].tolist() == [7, 21, 64]
    # schedule columns are the bucket's (shared by every slot)
    assert np.array_equal(tel["survivors"][0], tel["survivors"][1])


@pytest.mark.parametrize("phase", ["build", "swap"])
def test_cluster_estimators_telemetry_neutral(phase):
    n, k = 40, 2
    data = jax.random.normal(jax.random.key(6), (n, 4))
    pw = get_backend("reference").pairwise("l2")
    dist = pw(data, data)                                  # (n, n)
    meds = jnp.array([3, 29])
    to_meds = dist[:, meds]                                # (n, k)
    nearest = jnp.argmin(to_meds, axis=1)
    d1 = jnp.min(to_meds, axis=1)
    d2 = jnp.max(to_meds, axis=1)                          # k=2: the other one
    if phase == "build":
        est = build_delta(metric="l2", d1=d1)
    else:
        est = swap_delta(metric="l2", d1=d1, d2=d2, nearest=nearest, k=k)
    rounds = round_schedule(n, 15 * n)
    problem = HalvingProblem(data, est)
    off = run_halving(problem, rounds, key=jax.random.key(7))
    on = run_halving(problem, rounds, key=jax.random.key(7), telemetry=True)
    assert int(on.winner) == int(off.winner)
    assert np.array_equal(np.asarray(on.theta), np.asarray(off.theta),
                          equal_nan=True)
    assert off.telemetry is None
    tel = telemetry_to_host(on.telemetry)
    assert tel["pulls"].tolist() == [
        r.pulls for r in rounds[: on.r_stop + 1]]


# ------------------------- program cache neutrality --------------------------

def test_telemetry_compiles_once_then_never():
    data = jax.random.normal(jax.random.key(8), (45, 3))
    kw = dict(budget_per_arm=9, backend="reference")
    with instrument.deltas() as first:
        find_medoid(data, jax.random.key(9), telemetry=True, **kw)
        find_medoid(data, jax.random.key(9), **kw)
    # each variant is its own cached program — at most one trace apiece
    assert first.trace("medoid") <= 2
    with instrument.deltas() as rerun:
        find_medoid(data, jax.random.key(9), telemetry=True, **kw)
        find_medoid(data, jax.random.key(9), **kw)
    assert rerun.trace() == 0            # both variants already cached
    assert rerun.dispatch("medoid") == 2


def test_deltas_freeze_on_exit():
    data = jax.random.normal(jax.random.key(10), (19, 3))
    find_medoid(data, jax.random.key(11), budget_per_arm=7)   # prime cache
    with instrument.deltas() as d:
        find_medoid(data, jax.random.key(11), budget_per_arm=7)
        assert d.dispatch("medoid") == 1          # readable mid-block
    frozen = d.counters()
    find_medoid(data, jax.random.key(11), budget_per_arm=7)   # after exit
    assert d.counters() == frozen                 # exit froze the deltas
    assert d.dispatch("medoid") == 1


# ------------------------------ facade edges --------------------------------

def test_telemetry_requires_corr_sh():
    data = jnp.zeros((4, 2))
    with pytest.raises(ValueError, match="telemetry"):
        find_medoid(data, jax.random.key(0), algo="exact", telemetry=True)


def test_single_point_yields_empty_rows():
    res = find_medoid(jnp.zeros((1, 3)), jax.random.key(0), telemetry=True)
    assert res.medoid == 0 and res.pulls == 0
    assert set(res.telemetry) == set(telemetry.FIELDS)
    assert all(v.shape == (0,) for v in res.telemetry.values())


# ------------------------------ trace sessions -------------------------------

def test_trace_session_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    data = jax.random.normal(jax.random.key(12), (33, 4))
    with TraceSession(path, meta={"workload": "test"}) as sess:
        with sess.span("query"):
            res = find_medoid(data, jax.random.key(13), budget_per_arm=8,
                              telemetry=True)
        sess.record_result(res)
    summary = validate_trace(path)      # checks seq, schema, pull sums
    assert summary["selects"] == 1
    assert summary["rounds"] == len(_executed(33, 8 * 33))
    span = next(e for e in sess.events if e["event"] == "span")
    assert span["name"] == "query" and span["dur_s"] >= 0
    assert span["dispatches"].get("medoid") == 1
    with pytest.raises(RuntimeError):
        sess.event("late")              # closed sessions refuse writes


def test_validator_rejects_bad_pull_accounting(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    with TraceSession(path) as sess:
        sess.event("round", r=0, **{k: 1 for k in telemetry.FIELDS})
        sess.event("select", winner=0, pulls=999)    # != round sum
    with pytest.raises(ValueError, match="round records sum"):
        validate_trace(path)


# ------------------------------ serving metrics ------------------------------

def test_server_metrics_and_trace(tmp_path):
    from repro.launch.serve_medoid import MedoidServer, synthetic_trace

    queries = synthetic_trace(5, 8, 60, 4, seed=21)
    path = str(tmp_path / "srv.jsonl")
    with TraceSession(path) as sess:
        srv = MedoidServer(budget_per_arm=9, max_batch=4, seed=2, trace=sess)
        plain = MedoidServer(budget_per_arm=9, max_batch=4, seed=2)
        for q in queries:
            srv.submit(q)
            plain.submit(q)
        srv.drain()
        plain.drain()
    # tracing a server never changes its answers
    assert {r: q.medoid for r, q in srv.done.items()} \
        == {r: q.medoid for r, q in plain.done.items()}
    summary = validate_trace(path)
    assert summary["selects"] == 5
    snap = srv.metrics()
    assert sum(s["value"] for s in
               snap["medoid_answered_total"]["series"]) == 5
    occ = snap["medoid_batch_occupancy"]["series"]
    assert sum(s["count"] for s in occ) == srv.dispatches
    mpath = tmp_path / "srv.txt"
    mpath.write_text(srv.exposition())
    got = validate_exposition(str(mpath))
    assert got["families"] >= 7         # 7 server families + odometers
    assert "medoid_dispatch_seconds_bucket" in mpath.read_text()


def test_server_metrics_phase_split():
    m = ServerMetrics()
    m.record_submit("64x4")
    m.record_dispatch("64x4", wall_s=1.5, batch=2, slots=4,
                      pulls_per_request=100, waits_s=[0.0, 0.25],
                      compiled=True)
    m.record_dispatch("64x4", wall_s=0.002, batch=4, slots=4,
                      pulls_per_request=100, waits_s=[0.0, 0.0, 0.25, 0.5],
                      compiled=False)
    snap = m.snapshot()
    (wait,) = snap["medoid_queue_wait_seconds"]["series"]
    assert (wait["count"], wait["sum"]) == (6, 1.0)
    series = {tuple(sorted(s["labels"].items())): s["value"]
              for s in snap["medoid_dispatches_total"]["series"]}
    assert series[(("bucket", "64x4"), ("phase", "compile"))] == 1
    assert series[(("bucket", "64x4"), ("phase", "steady"))] == 1
    assert sum(s["value"] for s in
               snap["medoid_pulls_total"]["series"]) == 600
    with pytest.raises(ValueError, match="only go up"):
        m.requests.labels("64x4").inc(-1)


def test_cluster_service_routes():
    from repro.cluster.service import ClusterService, kmedoids_via_service

    data = jax.random.normal(jax.random.key(14), (96, 5))
    res, srv = kmedoids_via_service(data, 3, jax.random.key(15))
    svc = ClusterService(srv)
    assert svc.routes() == ("/buckets", "/metrics", "/stats")
    stats = svc.handle("/stats")
    assert stats["answered"] == len(srv.done)
    assert "medoid_requests_total" in stats["metrics"]
    assert "# TYPE medoid_requests_total counter" in svc.handle("/metrics")
    assert svc.handle("/buckets")["dispatches"] == srv.dispatches
    with pytest.raises(KeyError, match="/nope"):
        svc.handle("/nope")


# --------------------------------- CLI smoke ---------------------------------

def test_launch_medoid_reports_device(capsys):
    from repro.launch import medoid as launch_medoid

    launch_medoid.main(["--n", "16", "--d", "4", "--budget-per-arm", "8"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    dev = jax.devices()[0]
    assert (out["platform"], out["device_kind"], out["device_count"]) == \
        (dev.platform, dev.device_kind, len(jax.devices()))


def test_launch_medoid_distributed_needs_two_devices(monkeypatch):
    from repro.launch import medoid as launch_medoid

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    with pytest.raises(ValueError, match="at least 2 devices"):
        launch_medoid.run(16, 4, "", 8, "planted", distributed=True)


def test_launch_medoid_trace_cli(tmp_path, capsys):
    from repro.launch import medoid as launch_medoid
    from repro.obs.validate import main as validate_main

    tpath = str(tmp_path / "m.jsonl")
    mpath = str(tmp_path / "m.txt")
    launch_medoid.main(["--n", "48", "--d", "4", "--budget-per-arm", "8",
                        "--trace", tpath, "--metrics-out", mpath])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert sum(out["telemetry"]["pulls"]) == out["pulls_scheduled"]
    assert validate_main([tpath, mpath]) == 0
    assert validate_trace(tpath)["selects"] == 1
