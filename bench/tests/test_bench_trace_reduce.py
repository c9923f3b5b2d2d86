"""The trace reduction: busy union, idle share, kernel time by name and
idle-gap attribution, on hand-made events and on a small recorded trace."""
import os

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Op, Span

DATA = os.path.join(os.path.dirname(__file__), "data")


def op(text, start, dur):
    return Op(text, float(start), float(dur))


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)]) == \
        [(0, 4), (5, 7), (9, 10)]


def test_gaps_cover_what_busy_leaves():
    busy = [(2, 4), (6, 7)]
    assert tr.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps([(0, 10)], 0, 10) == []


def test_summary_inside_the_window_span():
    devices = {"/device:TPU:0": [
        op("%fusion.1 = f32[8]", 0, 100),    # before the window: cut off
        op('%kernel_centrality_sums.2 = f32[128,1] custom-call(), '
           'custom_call_target="tpu_custom_call"', 150, 300),
        op("%fusion.3 = f32[8]", 400, 100),  # overlaps the kernel
        op("%fusion.4 = f32[8]", 800, 100)]}
    spans = [Span("window", 100, 1000), Span("call", 100, 600),
             Span("find_medoid", 120, 400), Span("wait_arrival", 700, 90)]
    s = tr.reduce(devices, spans)
    assert s.window_s == pytest.approx(1000e-9)
    # busy: [150, 500) and [800, 900) inside [100, 1100)
    assert s.busy_s == pytest.approx(450e-9)
    assert s.idle_share_pct() == pytest.approx(55.0)
    kernel = (("%kernel_centrality_sums", "tpu_custom_call"),)
    assert s.kernel_s(kernel) == pytest.approx(300e-9)
    assert s.kernel_s((("%kernel_topk_smallest",),)) == 0.0
    gaps = s.gap_seconds()
    # [100,150) in find_medoid, [500,800) mid 650 in call, [900,1100) none
    assert gaps["find_medoid"] == pytest.approx(50e-9)
    assert gaps["call"] == pytest.approx(300e-9)
    assert gaps["(none)"] == pytest.approx(200e-9)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "kernel_centrality_sums"
    assert len(b["device_ops"]) <= tr.TOP and len(b["idle_gaps"]) <= tr.TOP


def test_busy_is_averaged_over_devices():
    devices = {"/device:TPU:0": [op("a", 0, 50)],
               "/device:TPU:1": [op("a", 0, 100)]}
    s = tr.reduce(devices, [Span("window", 0, 100)])
    assert s.busy_s == pytest.approx(75e-9)
    assert s.idle_share_pct() == pytest.approx(25.0)


def test_no_device_plane_reads_nothing():
    s = tr.reduce({}, [Span("window", 0, 100)])
    assert s.busy_s == 0.0 and s.idle_share_pct() is None
    assert s.kernel_s((("x",),)) == 0.0


def test_own_time_leaves_out_nested_operations():
    loop = op("%while.3 = (s32[]) while(...)", 0, 100)
    body = [op("%fusion.1 = f32[8]", 10, 20), op("%fusion.2 = f32[8]", 40, 30)]
    inner = op("%copy.9 = f32[8]", 45, 5)
    own = {o.label: t for o, t in tr.self_times([loop, *body, inner])}
    assert own[loop.label] == 50 and own[body[1].label] == 25
    assert own[inner.label] == 5 and own[body[0].label] == 20
    s = tr.reduce({"/device:TPU:0": [loop, *body, inner]},
                  [tr.Span("window", 0, 100)])
    assert s.op_seconds() == pytest.approx(
        {"while": 50e-9, "fusion": 45e-9, "copy": 5e-9})
    assert s.busy_s == pytest.approx(100e-9)


RECORDED = os.path.join(DATA, "pipeline_tiny.xplane.pb")
KERNEL = (("kernel_centrality_sums", "tpu_custom_call"),)


@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on a TPU v5e by ``bench/run.py --trace 1`` of the
    pipeline cell at n=1024, d=512 (a window of a few queries); the
    checkout's directory in its source locations reads ``/srv/bench/``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(RECORDED)
    return tr.summarize(RECORDED), data


def test_recorded_trace_busy_union_and_idle_share(recorded):
    s, data = recorded
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    ops = [(e.start_ns, e.start_ns + e.duration_ns) for line in plane.lines
           if line.name == "XLA Ops" for e in line.events]
    # busy by a second route: sweep over every op boundary in the window
    edges = sorted({s.lo, s.hi, *[t for iv in ops for t in iv
                                  if s.lo <= t <= s.hi]})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(lo <= a and b <= hi for lo, hi in ops))
    assert s.busy_s == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0.0 < s.busy_s <= s.window_s
    assert s.idle_share_pct() == pytest.approx(
        100.0 * (1.0 - s.busy_s / s.window_s))


def test_recorded_trace_kernel_time_by_name(recorded):
    s, _ = recorded
    kernel = s.kernel_s(KERNEL)
    assert 0.0 < kernel < s.busy_s
    own = s.op_seconds()
    assert own["kernel_centrality_sums"] == pytest.approx(kernel, rel=1e-9)
    # own times add up to the busy time (no op counted twice; ops that
    # straddle the window's edges are clipped in busy time only)
    assert sum(own.values()) == pytest.approx(s.busy_s, rel=1e-3)


def test_recorded_trace_gap_attribution(recorded):
    s, _ = recorded
    gaps = s.gap_seconds()
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s,
                                               rel=1e-6)
    names = {sp.name for sp in s.spans} | {"(none)"}
    assert set(gaps) <= names
    assert {"call", "find_medoid"} <= {sp.name for sp in s.spans}
    b = s.breakdown()
    assert b["device_ops"][0][0] == max(s.op_seconds(),
                                        key=s.op_seconds().get)


def test_recorded_trace_readings_as_recorded(recorded):
    """The numbers this trace gave when it was committed."""
    s, _ = recorded
    assert s.window_s == pytest.approx(0.052082325, rel=1e-9)
    assert s.busy_s == pytest.approx(0.019398631, rel=1e-9)
    assert s.kernel_s(KERNEL) == pytest.approx(0.014972069, rel=1e-9)
    assert s.idle_share_pct() == pytest.approx(62.7539074, rel=1e-6)
    assert max(s.gap_seconds(), key=s.gap_seconds().get) == "find_medoid"
