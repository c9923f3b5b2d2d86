"""Always-on observability of the serving path and the round loop.

* ``medoid.*`` profiler spans: a server step emits its phases in order,
  nested in ``medoid.step``, and the spans of one dispatch share its ids;
* the winner gap rides the plain ragged program (``telemetry="gap"``): it
  equals the per-round telemetry's output-round gap bit for bit, answers
  are unchanged, and a gaps-on server never traces or pulls the per-round
  variant;
* the work tally: ``called`` / ``computed`` distance terms match a hand
  count of the banded schedule and the kernel tiles;
* queue wait is measured in seconds on the server's clock.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import find_medoid
from repro.core.corr_sh import ragged_medoids
from repro.engine import instrument, round_schedule, stop_round
from repro.launch import serve_medoid
from repro.launch.serve_medoid import MedoidServer

pytestmark = pytest.mark.obs

BACKENDS = ("reference", "pallas_pairwise", "pallas_fused",
            "pallas_fused_topk")


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def _queries(n_list, d=4, seed=0):
    key = jax.random.key(seed)
    return [jax.random.normal(jax.random.fold_in(key, i), (n, d))
            for i, n in enumerate(n_list)]


# ------------------------------ profiler spans -------------------------------

def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("medoid."):
                    out.append((ev.name[len("medoid."):], ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_server_step_emits_its_spans_in_order(tmp_path):
    srv = MedoidServer(budget_per_arm=8, max_batch=4)
    qs = _queries((20, 30, 25))
    srv.submit(qs[0])                       # compile outside the trace
    srv.step()
    jax.profiler.start_trace(str(tmp_path))
    rids = [srv.submit(q) for q in qs]
    srv.step()
    jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    names = [s[0] for s in spans]
    assert names == ["submit"] * 3 + ["step", "schedule", "pack", "dispatch",
                                      "wait", "account"]
    assert [s[3]["rid"] for s in spans[:3]] == rids
    step = spans[3]
    assert step[3]["step"] == 2
    for name, start, end, _ in spans[4:]:
        assert step[1] <= start and end <= step[2], name
    for (_, _, end_a, _), (_, start_b, _, _) in zip(spans[4:], spans[5:]):
        assert end_a <= start_b            # phases follow one another
    ids = {name: stats for name, _, _, stats in spans[6:]}
    assert ids["dispatch"]["rids"] == " ".join(map(str, rids))
    assert ids["dispatch"]["dispatch"] == ids["wait"]["dispatch"] \
        == ids["account"]["dispatch"] == 2


def test_span_helper_is_the_same_annotation_without_a_profiler():
    from repro.obs import span

    with span("step", step=1) as ann:
        pass
    assert isinstance(ann, jax.profiler.TraceAnnotation)


# ------------------------------- winner gaps ---------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_gap_output_equals_the_telemetry_gap(backend):
    data = jnp.stack([jnp.pad(q, ((0, 32 - q.shape[0]), (0, 0)))
                      for q in _queries((32, 19, 2, 27), seed=3)])
    lengths = jnp.asarray([32, 19, 2, 27], jnp.int32)
    budget = 8 * 32
    kw = dict(budget=budget, backend=backend)
    key = jax.random.key(4)
    plain = ragged_medoids(data, lengths, key, **kw)
    med_g, gap = ragged_medoids(data, lengths, key, telemetry="gap", **kw)
    med_t, tel = ragged_medoids(data, lengths, key, telemetry=True, **kw)
    # answers bit-identical with the gap output on and off
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(med_g))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(med_t))
    stop = stop_round(round_schedule(32, budget))
    want = np.asarray(tel["gap"])[:, stop]
    assert gap.shape == (4,) and gap.dtype == jnp.float32
    # bit for bit, NaN (a one-arm output round) included
    np.testing.assert_array_equal(np.asarray(gap).view(np.int32),
                                  want.view(np.int32))


def test_quantized_gap_output_equals_the_telemetry_gap():
    data = jnp.stack(_queries((16, 16), seed=5))
    lengths = jnp.asarray([16, 11], jnp.int32)
    kw = dict(budget=8 * 16, precision="bf16")
    key = jax.random.key(6)
    med_g, ver_g, gap = ragged_medoids(data, lengths, key, telemetry="gap",
                                       **kw)
    med_t, ver_t, tel = ragged_medoids(data, lengths, key, telemetry=True,
                                       **kw)
    np.testing.assert_array_equal(np.asarray(med_g), np.asarray(med_t))
    np.testing.assert_array_equal(np.asarray(ver_g), np.asarray(ver_t))
    stop = stop_round(round_schedule(16, 8 * 16))
    np.testing.assert_array_equal(
        np.asarray(gap).view(np.int32),
        np.asarray(tel["gap"])[:, stop].view(np.int32))


def test_gaps_on_server_never_runs_the_telemetry_variant(monkeypatch):
    def refuse(tel):
        raise AssertionError("telemetry pulled without a TraceSession")

    monkeypatch.setattr(serve_medoid, "telemetry_to_host", refuse)
    qs = _queries((40, 33, 61), seed=7)
    with instrument.deltas() as d:
        srv = MedoidServer(budget_per_arm=8, max_batch=2, seed=1)
        srv.warmup([(40, 4)])
        for q in qs:
            srv.submit(q)
        srv.drain()
    assert d.trace("telemetry") == 0
    assert srv.recompiles == 0
    assert all(np.isfinite(q.gap) for q in srv.done.values())
    # the same requests through the per-round variant record the same gaps
    from repro.obs import TraceSession

    monkeypatch.undo()
    with TraceSession() as sess:
        traced = MedoidServer(budget_per_arm=8, max_batch=2, seed=1,
                              trace=sess)
        for q in qs:
            traced.submit(q)
        traced.drain()
    assert {r: (q.medoid, q.gap) for r, q in srv.done.items()} \
        == {r: (q.medoid, q.gap) for r, q in traced.done.items()}


# -------------------------------- work tally ---------------------------------

def _hand_count(padded):
    """n = 257, 16 pulls per arm, d = 40: the schedule keeps 257, 129, 65,
    33, 17, 9, 5, 3, 2 arms against 1, 3, 7, 13, 26, 50, 91, 152, 228
    references; rounds 0-7 run as three scan bands of widths 257, 33, 5
    (reference buffers 7, 50, 152; 3, 3 and 2 trips) and round 8 is the
    output round, 2 arms x 228 references. The fused l1 kernel pads each
    row axis to the sublane multiple (8) in equal tiles of at most 128
    candidates and 512 references (257 -> 3 x 88), and d = 40 to one
    128-lane chunk."""
    blocks = [(257, 7, 3), (33, 50, 3), (5, 152, 2), (2, 228, 1)]

    def pad(v, b):
        return -(-v // b) * b

    def rows(v, cap):
        tiles = -(-v // cap)
        return tiles * pad(-(-v // tiles), 8)

    called = sum(r * t * trips for r, t, trips in blocks) * 40
    if not padded:
        return called, called
    computed = sum(rows(r, 128) * rows(t, 512) * trips
                   for r, t, trips in blocks) * pad(40, 128)
    return called, computed


@pytest.mark.parametrize("backend", ["reference", "pallas_fused"])
def test_work_tally_matches_a_hand_count(backend):
    x = jax.random.normal(jax.random.key(8), (257, 40))
    with instrument.deltas() as d:
        for i in range(2):
            find_medoid(x, jax.random.key(i), metric="l1",
                        budget_per_arm=16, backend=backend)
    called, computed = _hand_count(backend != "reference")
    assert (called, computed) == ((492920, 2220032)
                                  if backend != "reference"
                                  else (492920, 492920))
    work = d.work("medoid")
    assert (work.called, work.computed) == (2 * called, 2 * computed)


def test_work_tally_counts_every_query_of_a_ragged_batch():
    data = jnp.stack(_queries((64, 64, 64), d=8, seed=9))
    lengths = jnp.asarray([64, 50, 3], jnp.int32)
    with instrument.deltas() as d:
        ragged_medoids(data[:1], lengths[:1], jax.random.key(0),
                       budget=8 * 64)
    one = d.work("ragged")
    with instrument.deltas() as d:
        ragged_medoids(data, lengths, jax.random.key(0), budget=8 * 64,
                       telemetry="gap")
    three = d.work("ragged")
    assert one.called > 0 and one.computed == one.called  # no tile
    assert (three.called, three.computed) == (3 * one.called,
                                              3 * one.computed)


# -------------------------------- queue wait ---------------------------------

def test_queue_wait_seconds_on_the_server_clock():
    clock = FakeClock(1.0)
    srv = MedoidServer(budget_per_arm=8, max_batch=2, clock=clock,
                       collect_gaps=False)
    qs = _queries((16, 16, 16), seed=10)
    srv.submit(qs[0])
    clock.t = 2.5
    srv.submit(qs[1])
    srv.submit(qs[2])
    clock.t = 5.0
    srv.step()                    # rids 0, 1: waited 4.0 and 2.5 s
    clock.t = 6.0
    srv.step()                    # rid 2: waited 3.5 s
    (series,) = srv.metrics()["medoid_queue_wait_seconds"]["series"]
    assert (series["count"], series["sum"]) == (3, 10.0)
    assert [srv.done[r].wait_steps for r in (0, 1, 2)] == [0, 0, 1]
    assert "medoid_queue_wait_steps" not in srv.metrics()
