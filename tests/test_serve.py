"""Live-corpus serving suite: mutable store, incremental maintenance, EDF.

The acceptance properties of the serving subsystem (ISSUE 9):

* **bit-identity** — on a long insert/delete stream, every served answer
  equals recomputing from scratch on that exact corpus version (the
  incremental centralities are exact, and re-runs are keyed by version);
* **O(n) kept mutations** — a mutation that keeps the incumbent costs one
  capacity-bucket n-vector of distance evaluations, asserted via the pull
  odometer on every update record;
* **no retrace on mutate** — an arbitrary mutation stream inside one
  capacity bucket reuses one compiled program per mutation kind (the
  ``"corpus"`` trace odometer stays flat), and re-runs reuse the ragged
  program of their bucket;
* **EDF scheduling** — earliest-deadline-first ordering, priority
  tie-breaks, shed-on-hopeless-deadline, FIFO default unchanged.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st
from repro.engine import instrument
from repro.serve.corpus import CorpusStore
from repro.serve.maintain import MaintainedMedoid
from repro.serve.scheduler import EdfPolicy, FifoPolicy, LatencyModel, \
    resolve_policy

pytestmark = pytest.mark.serve


def exact_cent(store: CorpusStore) -> np.ndarray:
    """From-scratch centralities of the live snapshot, in live-slot order
    (float32 host recompute — the reference a served answer is judged by)."""
    snap = store.snapshot().astype(np.float32)
    d = np.sqrt(np.maximum(
        ((snap[:, None, :] - snap[None, :, :]) ** 2).sum(-1), 0.0,
        dtype=np.float32))
    return d.sum(1)


def exact_slot(store: CorpusStore) -> int:
    """From-scratch exact medoid slot of the store's current version."""
    return int(store.live_slots()[exact_cent(store).argmin()])


def assert_eps_exact(store: CorpusStore, slot: int) -> None:
    """Served ``slot`` equals the from-scratch medoid, or (exact ties /
    float32 accumulation residue — the corpus-store precision caveat) its
    true centrality is within fractional tolerance of the true minimum."""
    if slot == exact_slot(store):
        return
    cent = exact_cent(store)
    pos = int(np.searchsorted(store.live_slots(), slot))
    lo = float(cent.min())
    assert float(cent[pos]) <= lo + 1e-3 * max(1.0, abs(lo)), \
        f"served slot {slot} is not an eps-exact medoid"


def exact_budget(n_bucket: int) -> int:
    # budget_per_arm >= n_bucket * ceil(log2 n_bucket): every round exact
    return n_bucket * max(1, int(np.ceil(np.log2(n_bucket))))


# ---------------------------------------------------------------------------
# corpus store
# ---------------------------------------------------------------------------

class TestCorpusStore:
    def test_bootstrap_matches_exact(self):
        rng = np.random.default_rng(0)
        data = rng.normal(size=(11, 5)).astype(np.float32)
        store = CorpusStore.from_points(data)
        assert store.n == 11 and store.capacity == 16
        assert store.exact_medoid_slot == exact_slot(store)
        assert store.init_pulls == 16 * 16

    def test_mutations_track_exact_centralities(self):
        rng = np.random.default_rng(1)
        store = CorpusStore.from_points(
            rng.normal(size=(9, 4)).astype(np.float32))
        for step in range(30):
            if store.n <= 4 or (store.n < 14 and rng.random() < 0.6):
                store.insert(rng.normal(size=4).astype(np.float32))
            else:
                store.delete(int(rng.choice(store.live_slots())))
            assert store.exact_medoid_slot == exact_slot(store), \
                f"winner drifted at step {step}"
        assert store.version == 30

    def test_slot_recycling_is_deterministic(self):
        store = CorpusStore(3, capacity=8)
        s0 = store.insert(np.ones(3, np.float32))
        s1 = store.insert(np.full(3, 2, np.float32))
        assert (s0, s1) == (0, 1)         # lowest free slot first
        store.delete(s0)
        assert store.insert(np.zeros(3, np.float32)) == 0   # recycled

    def test_growth_doubles_and_preserves_slots(self):
        rng = np.random.default_rng(2)
        store = CorpusStore.from_points(
            rng.normal(size=(8, 3)).astype(np.float32))
        assert store.capacity == 8 and not store._free
        slots_before = store.live_slots().tolist()
        s = store.insert(rng.normal(size=3).astype(np.float32))
        assert store.capacity == 16 and store.grows == 1
        assert s == 8                      # new slots extend, never remap
        assert store.live_slots().tolist() == slots_before + [8]
        assert store.exact_medoid_slot == exact_slot(store)

    def test_mutation_cost_is_one_capacity_vector(self):
        rng = np.random.default_rng(3)
        store = CorpusStore.from_points(
            rng.normal(size=(10, 4)).astype(np.float32))
        before = store.mutation_pulls
        store.insert(rng.normal(size=4).astype(np.float32))
        assert store.mutation_pulls - before == store.capacity
        before = store.mutation_pulls
        store.delete(0)
        assert store.mutation_pulls - before == store.capacity

    def test_no_retrace_within_capacity_bucket(self):
        rng = np.random.default_rng(4)
        store = CorpusStore.from_points(
            rng.normal(size=(10, 4)).astype(np.float32))
        # warm both mutation kinds at this capacity, then an arbitrary
        # stream must never trace again
        store.insert(rng.normal(size=4).astype(np.float32))
        store.delete(0)
        with instrument.deltas() as d:
            for _ in range(20):
                if store.n < 14 and rng.random() < 0.6:
                    store.insert(rng.normal(size=4).astype(np.float32))
                elif store.n > 4:
                    store.delete(int(rng.choice(store.live_slots())))
            assert store.capacity == 16    # stayed inside the bucket
        assert d.trace("corpus") == 0
        assert d.dispatch("corpus") == 20

    def test_rejects_bad_input(self):
        store = CorpusStore(4)
        with pytest.raises(ValueError):
            store.insert(np.zeros(3, np.float32))     # wrong d
        with pytest.raises(ValueError):
            store.delete(0)                            # not live
        with pytest.raises(ValueError):
            CorpusStore(0)
        with pytest.raises(ValueError):
            CorpusStore(4, metric="nope")


# ---------------------------------------------------------------------------
# incremental maintenance: the acceptance stream
# ---------------------------------------------------------------------------

class TestMaintainedMedoid:
    def test_500_step_stream_every_answer_exact_and_On_when_kept(self):
        """THE acceptance test: a 500-step insert/delete stream where every
        served answer equals the from-scratch exact medoid of that corpus
        version, kept-incumbent mutations cost exactly one capacity
        n-vector, and no mutation inside a capacity bucket retraces."""
        rng = np.random.default_rng(7)
        # capacity pre-sized to the stream's bucket (no mid-stream growth —
        # growth legitimately traces new shapes and has its own test), and
        # n kept in [10, 16] so every re-run shares one ragged bucket
        store = CorpusStore.from_points(
            rng.normal(size=(12, 4)).astype(np.float32), capacity=32)
        mm = MaintainedMedoid(store, budget_per_arm=exact_budget(32), seed=3)
        # warm every program this stream can touch: both mutation kinds at
        # this capacity (the bootstrap already ran the re-run path)
        mm.insert(rng.normal(size=4).astype(np.float32))
        mm.delete(int(rng.choice(store.live_slots())))
        with instrument.deltas() as d:
            for step in range(500):
                if store.n <= 10 or (store.n < 16 and rng.random() < 0.55):
                    upd = mm.insert(rng.normal(size=4).astype(np.float32))
                else:
                    upd = mm.delete(int(rng.choice(store.live_slots())))
                slot, version = mm.query()
                assert slot == upd.medoid_slot
                assert slot == exact_slot(store), \
                    f"served answer wrong at step {step} (version {version})"
                if not upd.reran:
                    assert upd.reason == "kept"
                    assert upd.pulls == store.capacity, \
                        "kept mutation must cost exactly one n-vector"
            assert store.capacity == 32    # stream stayed in one bucket
        # no mutation inside the capacity bucket traced ANY program: the
        # corpus mutation kernels and the re-run's gather + ragged programs
        # were all warmed before the stream started
        assert d.trace("corpus") == 0
        assert d.trace("ragged") == 0
        assert mm.kept > 0 and mm.reruns > 0      # both paths exercised

    def test_rerun_bit_identical_to_fresh_run_on_same_version(self):
        """A re-run's answer is reproducible from (seed, version) alone:
        an independent MaintainedMedoid adopting a copy of the same corpus
        at the same version serves the identical slot."""
        rng = np.random.default_rng(8)
        data = rng.normal(size=(13, 6)).astype(np.float32)
        a = MaintainedMedoid(CorpusStore.from_points(data),
                             budget_per_arm=8, seed=11)
        b = MaintainedMedoid(CorpusStore.from_points(data),
                             budget_per_arm=8, seed=11)
        # modest budget (NOT the exact regime): equality must come from the
        # version-keyed rerun protocol, not from exactness
        for step in range(12):
            x = rng.normal(size=6).astype(np.float32)
            ua, ub = a.insert(x), b.insert(x)
            assert ua == ub
            assert a.query() == b.query()

    def test_deleted_incumbent_forces_rerun(self):
        rng = np.random.default_rng(9)
        store = CorpusStore.from_points(
            rng.normal(size=(10, 4)).astype(np.float32))
        mm = MaintainedMedoid(store, budget_per_arm=exact_budget(16))
        incumbent = mm.medoid_slot
        upd = mm.delete(incumbent)
        assert upd.reran and upd.reason == "deleted_incumbent"
        assert mm.query()[0] == exact_slot(store)

    def test_empty_and_refill(self):
        mm = MaintainedMedoid(d=3, budget_per_arm=exact_budget(8))
        assert mm.query() == (None, 0)
        mm.insert(np.zeros(3, np.float32))
        assert mm.query()[0] == 0
        upd = mm.delete(0)
        assert upd.reason == "emptied" and mm.query()[0] is None
        mm.insert(np.ones(3, np.float32))
        assert mm.query()[0] is not None

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10_000))
    def test_interleaving_linearizability(self, seed):
        """Property: ANY interleaving of inserts and deletes serves, after
        every mutation, the (eps-)exact medoid of that corpus version —
        i.e. the mutable store is linearizable against
        recompute-from-scratch, up to the float32 tie caveat."""
        rng = np.random.default_rng(seed)
        n0 = int(rng.integers(1, 10))
        store = CorpusStore.from_points(
            rng.normal(size=(n0, 3)).astype(np.float32))
        mm = MaintainedMedoid(store, budget_per_arm=exact_budget(32))
        for _ in range(25):
            if store.n == 0 or rng.random() < 0.6:
                mm.insert(rng.normal(size=3).astype(np.float32))
            else:
                mm.delete(int(rng.choice(store.live_slots())))
            slot, _ = mm.query()
            if store.n == 0:
                assert slot is None
            else:
                assert_eps_exact(store, slot)

    def test_facade_builder(self):
        from repro.api import maintain_medoid

        rng = np.random.default_rng(10)
        mm = maintain_medoid(rng.normal(size=(9, 4)).astype(np.float32),
                             budget_per_arm=exact_budget(16))
        assert mm.query()[0] == exact_slot(mm.store)
        mm2 = maintain_medoid(d=4)
        assert mm2.query() == (None, 0)
        with pytest.raises(ValueError):
            maintain_medoid()
        with pytest.raises(ValueError):
            maintain_medoid(d=4, algo="exact")


# ---------------------------------------------------------------------------
# scheduling: latency model + policies (pure host objects)
# ---------------------------------------------------------------------------

class _Req:
    def __init__(self, rid, bucket="64x8", priority=0, deadline_s=None):
        self.rid = rid
        self.bucket = bucket
        self.priority = priority
        self.deadline_s = deadline_s


def _bkey(r):
    return r.bucket


class TestScheduling:
    def test_resolve_policy(self):
        assert isinstance(resolve_policy("fifo"), FifoPolicy)
        assert isinstance(resolve_policy("edf"), EdfPolicy)
        p = EdfPolicy()
        assert resolve_policy(p) is p
        with pytest.raises(ValueError):
            resolve_policy("lifo")
        with pytest.raises(TypeError):
            resolve_policy(42)

    def test_latency_model_never_invents(self):
        from repro.obs import ServerMetrics

        m = ServerMetrics()
        model = LatencyModel(m, quantile=0.9)
        assert model.estimate("64x8", compiled=True) is None
        assert model.estimate("64x8", compiled=False) is None
        # steady data for one bucket; unseen buckets price as worst compile
        m.latency.labels("64x8", "steady").observe(0.004)
        m.latency.labels("64x8", "compile").observe(1.7)
        assert model.estimate("64x8", compiled=True) == pytest.approx(0.005)
        assert model.estimate("256x8", compiled=False) == pytest.approx(2.0)

    def test_fifo_is_arrival_order_bucket_group(self):
        q = [_Req(0, "a"), _Req(1, "b"), _Req(2, "a"), _Req(3, "a")]
        batch, rest, shed = FifoPolicy().select(
            q, now=0.0, max_batch=2, bucket_key=_bkey,
            estimate=lambda r: None)
        assert [r.rid for r in batch] == [0, 2]     # head's bucket-mates
        assert [r.rid for r in rest] == [1, 3]
        assert shed == []

    def test_edf_orders_by_deadline_then_priority_then_arrival(self):
        q = [_Req(0, "a", deadline_s=9.0), _Req(1, "a", deadline_s=5.0),
             _Req(2, "a", deadline_s=5.0, priority=3), _Req(3, "a")]
        batch, rest, shed = EdfPolicy().select(
            q, now=0.0, max_batch=3, bucket_key=_bkey,
            estimate=lambda r: None)
        # earliest deadline first; priority breaks the 5.0 tie; undated last
        assert [r.rid for r in batch] == [2, 1, 0]
        assert [r.rid for r in rest] == [3]
        assert shed == []

    def test_edf_picks_most_urgent_bucket(self):
        q = [_Req(0, "a"), _Req(1, "b", deadline_s=1.0), _Req(2, "b")]
        batch, rest, _ = EdfPolicy().select(
            q, now=0.0, max_batch=4, bucket_key=_bkey,
            estimate=lambda r: None)
        assert [r.rid for r in batch] == [1, 2]     # urgent bucket's mates
        assert [r.rid for r in rest] == [0]

    def test_edf_sheds_hopeless_deadlines(self):
        q = [_Req(0, deadline_s=0.5),                 # already passed
             _Req(1, deadline_s=2.0),                 # infeasible: est 1.5
             _Req(2, deadline_s=9.0), _Req(3)]        # fine / best-effort
        batch, rest, shed = EdfPolicy().select(
            q, now=1.0, max_batch=4, bucket_key=_bkey,
            estimate=lambda r: 1.5)
        assert [r.rid for r in shed] == [0, 1]
        assert [r.rid for r in batch] == [2, 3]
        assert rest == []

    def test_edf_never_sheds_unpriced_requests(self):
        q = [_Req(0, deadline_s=2.0)]
        batch, _, shed = EdfPolicy().select(
            q, now=1.99, max_batch=1, bucket_key=_bkey,
            estimate=lambda r: None)
        assert shed == [] and [r.rid for r in batch] == [0]


# ---------------------------------------------------------------------------
# the server: policies, deadlines, gaps, warmup
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


class TestMedoidServer:
    def test_edf_serves_earliest_deadline_first(self):
        from repro.launch.serve_medoid import MedoidServer

        clock = FakeClock()
        srv = MedoidServer(budget_per_arm=8, max_batch=2, policy="edf",
                           clock=clock, collect_gaps=False)
        key = jax.random.key(0)
        qa = jax.random.normal(key, (16, 4))
        qb = jax.random.normal(jax.random.fold_in(key, 1), (64, 4))
        r0 = srv.submit(qa)                                 # best-effort
        r1 = srv.submit(qb, deadline_s=50.0)
        r2 = srv.submit(qb, deadline_s=10.0, priority=1)    # most urgent
        first = srv.step()
        # the urgent 64-bucket group went first despite arriving last
        assert {q.rid for q in first} == {r1, r2}
        second = srv.step()
        assert [q.rid for q in second] == [r0]
        assert srv.done[r2].deadline_met is True
        assert srv.done[r0].deadline_met is None            # no deadline
        assert srv.stats()["policy"] == "edf"

    def test_edf_sheds_expired_requests(self):
        from repro.launch.serve_medoid import MedoidServer

        clock = FakeClock(100.0)
        srv = MedoidServer(budget_per_arm=8, max_batch=2, policy="edf",
                           clock=clock, collect_gaps=False)
        key = jax.random.key(1)
        dead = srv.submit(jax.random.normal(key, (16, 4)), deadline_s=99.0)
        live = srv.submit(jax.random.normal(key, (16, 4)), deadline_s=999.0)
        out = srv.step()
        assert [q.rid for q in out] == [live]
        assert dead in srv.shed and srv.shed[dead].shed
        assert srv.shed[dead].medoid is None
        assert srv.stats()["shed"] == 1
        # shed ids stay burned: resubmitting the rid is a duplicate
        with pytest.raises(ValueError):
            srv.submit(jax.random.normal(key, (16, 4)), rid=dead)
        # metrics recorded the shed + missed deadline
        text = srv.exposition()
        assert "medoid_shed_total" in text
        assert 'medoid_deadline_total{bucket="16x4",outcome="missed"} 1' \
            in text

    def test_fifo_default_ignores_deadlines(self):
        from repro.launch.serve_medoid import MedoidServer

        srv = MedoidServer(budget_per_arm=8, max_batch=2,
                           collect_gaps=False)
        key = jax.random.key(2)
        r0 = srv.submit(jax.random.normal(key, (16, 4)))
        r1 = srv.submit(jax.random.normal(key, (64, 4)), deadline_s=0.001,
                        priority=99)
        out = srv.step()
        assert [q.rid for q in out] == [r0]       # arrival order, no shed
        assert srv.stats()["policy"] == "fifo" and not srv.shed
        srv.drain()
        assert srv.done[r1].deadline_met is False  # recorded, not acted on

    def test_warmup_covers_both_program_variants(self):
        from repro.launch.serve_medoid import MedoidServer

        # gap collection ON (the default): dispatches ride the plain
        # program with its winner-gap output — the variant warmup traces —
        # so a warmed server's first metered step must not trace
        srv = MedoidServer(budget_per_arm=8, max_batch=2)
        srv.warmup([(40, 6)])
        srv.submit(jax.random.normal(jax.random.key(3), (40, 6)))
        with instrument.deltas() as d:
            srv.step()
        assert d.trace("ragged") == 0
        assert srv.recompiles == 0

    def test_gap_histogram_lands_in_exposition_and_validates(self, tmp_path):
        from repro.launch.serve_medoid import MedoidServer
        from repro.obs.validate import validate_exposition

        srv = MedoidServer(budget_per_arm=8, max_batch=2)   # gaps on
        key = jax.random.key(4)
        for i in range(3):
            srv.submit(jax.random.normal(jax.random.fold_in(key, i), (32, 4)))
        srv.drain()
        assert all(q.gap is not None for q in srv.done.values())
        text = srv.exposition()
        assert "medoid_winner_gap_bucket" in text
        path = tmp_path / "metrics.txt"
        path.write_text(text)
        summary = validate_exposition(str(path))
        assert summary["samples"] > 0

    def test_gap_collection_keeps_answers_bit_identical(self):
        from repro.launch.serve_medoid import MedoidServer

        key = jax.random.key(5)
        queries = [jax.random.normal(jax.random.fold_in(key, i), (24, 4))
                   for i in range(4)]
        answers = {}
        for gaps in (False, True):
            srv = MedoidServer(budget_per_arm=8, max_batch=2, seed=9,
                               collect_gaps=gaps)
            for q in queries:
                srv.submit(q)
            srv.drain()
            answers[gaps] = [srv.done[r].medoid for r in sorted(srv.done)]
        assert answers[False] == answers[True]


# ---------------------------------------------------------------------------
# streaming cluster maintenance
# ---------------------------------------------------------------------------

class TestClusterStream:
    def test_arrivals_assigned_to_nearest_medoid(self):
        from repro.cluster.service import ClusterStream

        rng = np.random.default_rng(11)
        data = rng.normal(size=(60, 4)).astype(np.float32)
        cs = ClusterStream(data, 3, jax.random.key(0))
        pts = rng.normal(size=(5, 4)).astype(np.float32)
        meds_before = cs.data[cs.medoids].copy()
        out = cs.add(pts)
        want = np.linalg.norm(pts[:, None, :] - meds_before[None, :, :],
                              axis=-1).argmin(1)
        np.testing.assert_array_equal(out["assigned"], want)
        assert cs.n == 65 and cs.arrivals == 5
        assert sorted(set(want.tolist())) == out["affected"]

    def test_only_affected_clusters_rerefine(self):
        from repro.cluster.service import ClusterStream

        rng = np.random.default_rng(12)
        # two tight, well-separated blobs: arrivals near blob 1 only
        data = np.concatenate([
            rng.normal(size=(30, 3)).astype(np.float32) - 10.0,
            rng.normal(size=(30, 3)).astype(np.float32) + 10.0])
        cs = ClusterStream(data, 2, jax.random.key(1))
        blob1 = int(cs.labels[-1])
        other = 1 - blob1
        med_other = cs.medoids[other]
        out = cs.add(rng.normal(size=(6, 3)).astype(np.float32) + 10.0)
        assert out["affected"] == [blob1]
        assert cs.medoids[other] == med_other     # untouched cluster stable

    def test_assign_program_is_shape_bucketed(self):
        from repro.cluster.kmedoids import assign_to_medoids

        meds = np.eye(3, dtype=np.float32)
        rng = np.random.default_rng(13)
        # arrival sizes 3 and 7 share the padded 8-bucket: labels agree
        # with numpy and padded pulls are charged honestly
        for m in (3, 7):
            pts = rng.normal(size=(m, 3)).astype(np.float32)
            labels, d1, pulls = assign_to_medoids(pts, meds)
            want = np.linalg.norm(pts[:, None, :] - meds[None, :, :],
                                  axis=-1).argmin(1)
            np.testing.assert_array_equal(labels, want)
            assert pulls == 8 * 3

    def test_stream_route_on_cluster_service(self):
        from repro.cluster.service import ClusterService, ClusterStream
        from repro.launch.serve_medoid import MedoidServer

        rng = np.random.default_rng(14)
        srv = MedoidServer(budget_per_arm=8, collect_gaps=False)
        cs = ClusterStream(rng.normal(size=(40, 3)).astype(np.float32), 2,
                           jax.random.key(2))
        svc = ClusterService(srv)
        assert "/stream" not in svc.routes()
        with pytest.raises(KeyError):
            svc.handle("/stream")
        svc.attach_stream(cs)
        assert "/stream" in svc.routes()
        cs.add(rng.normal(size=(4, 3)).astype(np.float32))
        payload = svc.handle("/stream")
        assert payload["arrivals"] == 4 and payload["n"] == 44
        assert payload["total_pulls"] == cs.pulls

    def test_refit_resets_from_current_store(self):
        from repro.cluster.service import ClusterStream

        rng = np.random.default_rng(15)
        cs = ClusterStream(rng.normal(size=(30, 3)).astype(np.float32), 2,
                           jax.random.key(3))
        cs.add(rng.normal(size=(10, 3)).astype(np.float32) + 5.0)
        fit = cs.refit()
        assert len(cs.labels) == cs.n == 40
        assert cs.medoids == list(fit.medoids)


# ---------------------------------------------------------------------------
# the mutation-stream driver (CI's serve-smoke entry)
# ---------------------------------------------------------------------------

class TestStreamDriver:
    def test_run_stream_verifies_and_artifacts_validate(self, tmp_path):
        from repro.obs import TraceSession
        from repro.obs.validate import validate_exposition, validate_trace
        from repro.serve.stream import StreamMetrics, exact_budget_per_arm, \
            run_stream

        rng = np.random.default_rng(16)
        store = CorpusStore.from_points(
            rng.normal(size=(10, 4)).astype(np.float32))
        mm = MaintainedMedoid(store,
                              budget_per_arm=exact_budget_per_arm(60, 8))
        trace_path = tmp_path / "stream.jsonl"
        metrics_path = tmp_path / "metrics.txt"
        metrics = StreamMetrics()
        with TraceSession(str(trace_path),
                          meta={"workload": "serve_stream"}) as session:
            out = run_stream(mm, steps=50, seed=16, verify=True,
                             metrics=metrics, trace=session)
        assert out["verified"] == 50
        # +1: adopting the pre-populated store cost one bootstrap re-run
        assert out["kept"] + out["reruns"] == 50 + 1
        metrics_path.write_text(metrics.exposition())
        assert validate_trace(str(trace_path))["selects"] == 50
        assert validate_exposition(str(metrics_path))["families"] >= 4
        text = metrics_path.read_text()
        assert "corpus_mutations_total" in text
        assert "corpus_pulls_total" in text
