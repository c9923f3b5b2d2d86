"""One-program execution model: retrace safety, donation, host-sync guards.

The PR-6 contract under test:

* **retrace exactly once** — repeated same-shape ``find_medoid`` /
  ``find_medoids_ragged`` / ``kmedoids`` calls trace one XLA program per
  distinct signature and zero afterwards (counter-based, via the monotone
  odometers of :mod:`repro.engine.instrument`);
* **donation only where it aliases, folded on CPU** — the corpus mutation
  programs donate (their outputs reuse the donated buffers), and the flag
  folds away on CPU; the facade's self-packed ragged path answers
  identically to the caller-packed path and leaves the caller's buffer
  alive;
* **no host syncs in the hot path** — the engine package, the device-path
  telemetry module (``repro.obs.telemetry``) and the cluster BUILD/SWAP
  phase kernels contain no ``.item()`` / ``np.asarray`` / ``device_get``
  (source-level guard, mirrored by the CI grep);
* **stacked schedules** — ``Schedule.stacked`` partitions exactly the
  scanned prefix ``[0, r_stop)`` into bands with the legacy entering sizes;
* **warmup + persistent cache** — a warmed ``MedoidServer`` serves known
  buckets with zero recompiles, and ``enable_compile_cache`` writes XLA
  cache entries a restarted process can reuse, in the directory
  ``JAX_COMPILATION_CACHE_DIR`` names.
"""
import inspect
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.api import find_medoid, find_medoids_ragged, kmedoids
from repro.core.bucketing import pack_queries
from repro.engine import instrument, programs
from repro.engine.schedule import Schedule, as_schedule, round_schedule

pytestmark = pytest.mark.engine

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "src")


# ------------------------------ retrace safety ------------------------------

def test_find_medoid_traces_exactly_once():
    data = jax.random.normal(jax.random.key(0), (37, 5))
    kw = dict(budget_per_arm=23, metric="l2", backend="reference")
    with instrument.deltas() as first:
        a = find_medoid(data, jax.random.key(1), **kw).medoid
    assert first.trace("medoid") <= 1  # 0 only if identical config ran earlier
    assert first.dispatch("medoid") == 1
    with instrument.deltas() as rerun:
        for i in range(3):          # same shape+config: never again
            b = find_medoid(data, jax.random.key(1), **kw).medoid
            assert b == a
    assert rerun.trace("medoid") == 0
    assert rerun.dispatch("medoid") == 3


def test_ragged_traces_once_per_bucket():
    qs = [jax.random.normal(jax.random.fold_in(jax.random.key(2), i), (n, 4))
          for i, n in enumerate((11, 29, 43))]   # all bucket to 64
    with instrument.deltas() as first:
        a = find_medoids_ragged(qs, key=jax.random.key(3), budget_per_arm=19)
    assert first.trace("ragged") <= 1
    with instrument.deltas() as rerun:
        b = find_medoids_ragged(qs, key=jax.random.key(3), budget_per_arm=19)
    assert [int(x) for x in a] == [int(x) for x in b]
    assert rerun.trace("ragged") == 0


def test_kmedoids_identical_rerun_traces_nothing():
    data = jax.random.normal(jax.random.key(4), (40, 6))
    res = kmedoids(data, 3, jax.random.key(5), build_budget_per_arm=13,
                   swap_budget_per_arm=13, refine_budget_per_arm=13)
    with instrument.deltas() as d:
        res2 = kmedoids(data, 3, jax.random.key(5), build_budget_per_arm=13,
                        swap_budget_per_arm=13, refine_budget_per_arm=13)
    assert d.trace() == 0                         # every program is cached
    assert d.counters()["traces"] == {}           # per-kind deltas agree
    assert (res2.medoids, res2.pulls, res2.swaps) == \
        (res.medoids, res.pulls, res.swaps)


# -------------------------------- donation ----------------------------------

def test_donation_flag_folds_away_on_cpu():
    cap, d = 8, 3
    args = (jnp.zeros((cap, d)), jnp.zeros((cap,)), jnp.zeros((cap,), bool),
            jnp.zeros((d,)), jnp.int32(0))
    text = programs.corpus_insert_program().lower(*args).as_text()
    # the insert program's outputs alias its donated (buf, cent, alive)
    # where donation is real; on CPU the flag folds away (no aliasing, no
    # warning spam)
    assert programs.donation_enabled() == (jax.default_backend() != "cpu")
    assert ("tf.aliasing_output" in text) == programs.donation_enabled()


def test_donated_facade_path_matches_nondonated():
    qs = [jax.random.normal(jax.random.fold_in(jax.random.key(6), i), (n, 4))
          for i, n in enumerate((17, 51))]
    # list input: the facade packs the buffer itself
    a = find_medoids_ragged(qs, key=jax.random.key(7), budget_per_arm=19)
    # caller-packed input: the caller's buffer must survive
    data, lens = pack_queries(qs)
    b = find_medoids_ragged(data, lens, jax.random.key(7), budget_per_arm=19)
    assert [int(x) for x in a] == [int(x) for x in b]
    assert data.shape == (2, 64, 4)               # still alive and readable
    assert bool(jnp.isfinite(data).all())


# ----------------------- host-sync source-level guard -----------------------

FORBIDDEN = (r"\.item\(", r"device_get", r"\bnp\.asarray")  # \b spares jnp.


def test_no_host_syncs_in_engine_package():
    import repro.engine.estimators
    import repro.engine.halving
    import repro.engine.programs
    import repro.engine.schedule
    import repro.obs.telemetry
    # repro.obs.telemetry is device-path: its stats ride the scanned round
    # loop, so it lives under the same guard as the engine package (the
    # host-side obs modules — trace/metrics — legitimately sync)
    for mod in (repro.engine.halving, repro.engine.estimators,
                repro.engine.programs, repro.engine.schedule,
                repro.obs.telemetry):
        src = inspect.getsource(mod)
        for pat in FORBIDDEN:
            assert not re.search(pat, src), f"{pat!r} found in {mod.__name__}"


def test_no_host_syncs_in_cluster_phase_kernels():
    from repro.cluster import kmedoids as km
    for fn in (km._build_step, km._build_scan, km._assign, km._top2_of,
               km._swap_argmin, km._exact_swap_delta, km._swap_sweep_impl):
        src = inspect.getsource(fn)
        for pat in FORBIDDEN:
            assert not re.search(pat, src), f"{pat!r} found in {fn.__name__}"


# ----------------------------- stacked schedules ----------------------------

def test_stacked_partitions_scanned_prefix():
    for n, per_arm in ((512, 16), (300, 10), (17, 3), (4096, 24)):
        sched = Schedule.from_budget(n, per_arm * n)
        stk = sched.stacked(n)
        # entering sizes follow the legacy halving recursion from n
        assert stk.sizes[0] == n
        for a, b in zip(stk.sizes, stk.sizes[1:]):
            assert b == math.ceil(a / 2)
        # bands tile [0, r_stop) exactly, in order, at the entering width
        covered = []
        for band in stk.bands:
            assert band.width == stk.sizes[band.start]
            assert band.ref_cap == max(band.num_refs)
            assert band.survivors == tuple(
                stk.sizes[band.start:band.start + len(band)])
            covered.extend(range(band.start, band.start + len(band)))
        assert covered == list(range(stk.r_stop))
        # the output round is static: exact or <= 2 entering arms
        rd = sched[stk.r_stop]
        assert rd.exact or stk.sizes[stk.r_stop] <= 2


def test_stacked_band_rounds_knob_and_errors():
    sched = Schedule.from_budget(512, 16 * 512)
    ones = sched.stacked(512, band_rounds=1)
    assert all(len(b) == 1 for b in ones.bands)
    big = sched.stacked(512, band_rounds=64)
    assert len(big.bands) == 1 and big.r_stop == ones.r_stop
    with pytest.raises(ValueError, match="band_rounds"):
        sched.stacked(512, band_rounds=0)
    with pytest.raises(ValueError, match="empty"):
        Schedule(()).stacked(1)
    assert as_schedule(round_schedule(64, 640)).rounds \
        == Schedule.from_budget(64, 640).rounds


# ------------------------- warmup + persistent cache ------------------------

def test_warmed_server_never_recompiles():
    from repro.launch.serve_medoid import MedoidServer, synthetic_trace

    srv = MedoidServer(budget_per_arm=21, max_batch=4, seed=0)
    trace = synthetic_trace(6, 16, 100, 5, seed=3)
    stats = srv.warmup(sorted({(q.shape[0], q.shape[1]) for q in trace}))
    assert set(stats) == {"buckets", "traces", "wall_s"}
    for q in trace:
        srv.submit(q)
    srv.drain()
    assert len(srv.done) == 6
    assert srv.recompiles == 0     # every bucket was pre-traced by warmup


@pytest.mark.slow
def test_persistent_cache_writes_entries(tmp_path):
    code = """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp
from repro.engine import programs
path = programs.enable_compile_cache()
fn = programs.medoid_program(budget=13 * 16)
fn(jnp.zeros((16, 3)), jax.random.key(0)).block_until_ready()
print(path)
print(len(os.listdir(path)))
"""
    env = dict(os.environ, PYTHONPATH=_SRC,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr
    path, count = out.stdout.strip().splitlines()[-2:]
    assert path == str(tmp_path)          # the variable wins, no other dir
    assert int(count) >= 1
