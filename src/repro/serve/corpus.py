"""Versioned, device-resident mutable corpus store.

A :class:`CorpusStore` owns three device buffers sized to a power-of-two
*capacity* bucket (:func:`repro.core.bucketing.bucket_n`):

* ``buf (cap, d)`` — the point rows (dead rows hold stale data, masked
  everywhere);
* ``cent (cap,)`` — the EXACT summed distance of each live slot to every
  live slot (+inf at dead slots), maintained incrementally;
* ``alive (cap,)`` — the live mask.

The host side keeps a slot **freelist** and a mirror of the live mask, so a
mutation never needs a device round-trip to find its row. Because every
mutation kernel (:func:`repro.engine.programs.corpus_insert_program` /
``corpus_delete_program``) operates on the full capacity bucket, the
compiled signature depends only on ``(cap, d, metric, backend)`` — an
arbitrary insert/delete stream inside one capacity bucket reuses exactly
one compiled program per mutation kind ("no retrace on mutate"; the
``"corpus"`` odometer of :mod:`repro.engine.instrument` pins it). When the
freelist runs dry the capacity bucket **doubles** (one growth program
copies the buffers into the larger bucket).

Each mutation costs one n-vector of distances (O(cap) pulls, counted in
:attr:`CorpusStore.mutation_pulls`) and updates the exact centrality of
every live point — which is precisely the information the incremental
medoid maintenance layer (:mod:`repro.serve.maintain`) needs to re-verify
its incumbent without re-running the bandit. ``version`` bumps on every
mutation; answers are always attributable to one exact corpus version.

Precision caveat: centralities accumulate in float32 (add a row on insert,
subtract it on delete), so after many mutations a slot's stored centrality
can differ from a fresh summation by float-cancellation residue (~1e-3
relative in long streams). On generic-position data the winner is
unaffected; under EXACT ties or near-ties inside that residue, the argmin
may resolve differently than a from-scratch recompute — the served point
is always an eps-exact medoid, not necessarily the same index.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.backend import get_backend
from repro.core.bucketing import DEFAULT_MIN_BUCKET, bucket_n
from repro.core.distances import METRICS
from repro.engine import instrument, programs


@dataclasses.dataclass(frozen=True)
class CorpusStats:
    """One snapshot of a store's accounting."""
    n: int                      # live points
    capacity: int               # power-of-two slot bucket
    version: int                # mutations applied so far
    inserts: int
    deletes: int
    grows: int                  # capacity doublings
    mutation_pulls: int         # distance evals spent on mutations
    init_pulls: int             # one-time bootstrap distance evals


class CorpusStore:
    """A mutable, versioned point store with exact incremental centralities.

    ``insert`` returns a stable integer **slot id** — the handle every
    answer speaks in (a snapshot index would shift under mutation). Slots
    are recycled through the freelist (lowest-numbered free slot first, so
    replayed streams hit identical slot sequences).
    """

    def __init__(self, d: int, *, metric: str = "l2",
                 backend: str = "reference",
                 min_bucket: int = DEFAULT_MIN_BUCKET,
                 capacity: Optional[int] = None,
                 precision: str = "fp32"):
        if d < 1:
            raise ValueError(f"need d >= 1, got {d}")
        if metric not in METRICS:
            raise ValueError(f"unknown metric {metric!r}; one of {METRICS}")
        if precision != "fp32":
            # Quantized store: every distance path — the bootstrap pass, the
            # per-mutation n-vectors, and maintenance re-runs (which use
            # store.backend) — rides the quantized backend for this
            # precision. The incremental centralities are then *quantized*-
            # exact: the float32-cancellation caveat above applies on top of
            # the quantization perturbation, so ties within the quantization
            # error may resolve differently than the fp32 store's.
            from repro import quant
            backend = quant.backend_for(precision, base=backend)
        get_backend(backend)            # fail at construction
        self.d = int(d)
        self.metric = metric
        self.backend = backend
        self.precision = precision
        self.min_bucket = int(min_bucket)
        cap = bucket_n(max(1, int(capacity or min_bucket)), self.min_bucket)
        self.buf = jnp.zeros((cap, self.d), jnp.float32)
        self.cent = jnp.full((cap,), jnp.inf, jnp.float32)
        self.alive = jnp.zeros((cap,), bool)
        self._alive_host = np.zeros((cap,), bool)
        self._free: list[int] = list(range(cap - 1, -1, -1))  # pop() -> 0
        self._winner = None             # device scalar: argmin(cent)
        self.version = 0
        self.inserts = self.deletes = self.grows = 0
        self.mutation_pulls = 0         # distance evals spent on mutations
        self.init_pulls = 0             # one-time bootstrap cost

    # ------------------------------ construction ---------------------------
    @classmethod
    def from_points(cls, data, **kwargs) -> "CorpusStore":
        """Build a store holding ``data (n, d)`` in slots ``0..n-1``. Seeds
        the exact centrality vector with ONE O(n^2) bootstrap pass (the
        only quadratic moment a store ever pays — every mutation after it
        is O(n))."""
        data = jnp.asarray(data, jnp.float32)
        if data.ndim != 2:
            raise ValueError(f"expected (n, d) data, got shape {data.shape}")
        n = int(data.shape[0])
        store = cls(int(data.shape[1]),
                    capacity=max(n, kwargs.pop("capacity", 0) or 0), **kwargs)
        if n:
            cap = store.capacity
            store.buf = store.buf.at[:n].set(data)
            store.alive = store.alive.at[:n].set(True)
            store._alive_host[:n] = True
            store._free = list(range(cap - 1, n - 1, -1))
            fn = programs.corpus_init_program(metric=store.metric,
                                              backend=store.backend)
            instrument.note_dispatch("corpus")
            store.cent, store._winner = fn(store.buf, store.alive)
            store.init_pulls = cap * cap
        return store

    # -------------------------------- queries ------------------------------
    @property
    def capacity(self) -> int:
        return int(self.buf.shape[0])

    @property
    def n(self) -> int:
        return int(self._alive_host.sum())

    def is_live(self, slot: int) -> bool:
        return 0 <= slot < self.capacity and bool(self._alive_host[slot])

    @property
    def exact_medoid_slot(self) -> Optional[int]:
        """Slot id of the exact medoid of the current version (one scalar
        device read; None for an empty store)."""
        if self.n == 0 or self._winner is None:
            return None
        return int(self._winner)

    def live_slots(self) -> np.ndarray:
        """Live slot ids, ascending — the store's canonical snapshot order
        (a from-scratch recompute on ``snapshot()`` speaks in positions of
        this array)."""
        return np.flatnonzero(self._alive_host)

    def snapshot(self) -> np.ndarray:
        """Host copy of the live points in slot order — the reference
        corpus a from-scratch recompute of this version runs on."""
        return np.asarray(self.buf)[self._alive_host]

    def gather(self, n_bucket: int) -> jnp.ndarray:
        """Pack the live rows into a dense ``(n_bucket, d)`` prefix (zero
        index padding past ``n``) via the cached gather program — the form
        the ragged engine consumes for a full re-run."""
        order = self.live_slots()
        if n_bucket < order.size:
            raise ValueError(f"n_bucket={n_bucket} < live count {order.size}")
        idx = np.zeros((n_bucket,), np.int32)
        idx[: order.size] = order
        instrument.note_dispatch("corpus")
        return programs.corpus_gather_program()(self.buf, jnp.asarray(idx))

    # ------------------------------- mutations ------------------------------
    def insert(self, x) -> int:
        """Insert one ``(d,)`` point; returns its slot id. Doubles the
        capacity bucket first if the freelist is dry. Updates every live
        centrality with the new point's distance row (one n-vector)."""
        x = jnp.asarray(x, jnp.float32)
        if x.shape != (self.d,):
            raise ValueError(f"expected a ({self.d},) point, got {x.shape}")
        if not self._free:
            self._grow()
        slot = self._free.pop()
        fn = programs.corpus_insert_program(metric=self.metric,
                                            backend=self.backend)
        instrument.note_dispatch("corpus")
        self.buf, self.cent, self.alive, self._winner = fn(
            self.buf, self.cent, self.alive, x, jnp.int32(slot))
        self._alive_host[slot] = True
        self.mutation_pulls += self.capacity
        self.inserts += 1
        self.version += 1
        return slot

    def delete(self, slot: int) -> None:
        """Delete a live slot (its id returns to the freelist). Backs the
        point's distance row out of every surviving centrality."""
        slot = int(slot)
        if not self.is_live(slot):
            raise ValueError(f"slot {slot} is not live")
        fn = programs.corpus_delete_program(metric=self.metric,
                                            backend=self.backend)
        instrument.note_dispatch("corpus")
        self.cent, self.alive, self._winner = fn(
            self.buf, self.cent, self.alive, jnp.int32(slot))
        self._alive_host[slot] = False
        self._free.append(slot)
        self.mutation_pulls += self.capacity
        self.deletes += 1
        self.version += 1

    def _grow(self) -> None:
        cap = self.capacity
        instrument.note_dispatch("corpus")
        self.buf, self.cent, self.alive = programs.corpus_grow_program()(
            self.buf, self.cent, self.alive)
        self._alive_host = np.concatenate(
            [self._alive_host, np.zeros((cap,), bool)])
        # new slots go UNDER existing free ids: lowest slot still pops first
        self._free = list(range(2 * cap - 1, cap - 1, -1)) + self._free
        self.grows += 1

    # -------------------------------- stats --------------------------------
    def stats(self) -> CorpusStats:
        return CorpusStats(n=self.n, capacity=self.capacity,
                           version=self.version, inserts=self.inserts,
                           deletes=self.deletes, grows=self.grows,
                           mutation_pulls=self.mutation_pulls,
                           init_pulls=self.init_pulls)
