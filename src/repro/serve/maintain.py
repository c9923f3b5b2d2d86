"""Incremental medoid maintenance over a mutable corpus.

BanditPAM's SWAP phase never trusts a bandit winner blindly: before a swap
is applied it is re-verified with ONE exact n-vector of distances
(:func:`repro.cluster.kmedoids._exact_swap_delta`). The same trick turns a
corpus mutation into an O(n) *incumbent re-verification* instead of a full
bandit re-run: the :class:`~repro.serve.corpus.CorpusStore` mutation
kernels already price the mutated point against the whole corpus (that one
n-vector) while updating the exact centrality of every live slot — so
after a mutation, whether the incumbent medoid survived is a single scalar
comparison, not a computation.

:class:`MaintainedMedoid` runs that protocol:

* mutation keeps the incumbent (the exact argmin didn't move) -> serve the
  incumbent unchanged, total cost one n-vector — O(n) pulls, counted in
  :attr:`incremental_pulls`;
* a challenger beats the incumbent, or the deleted point WAS the medoid ->
  fall back to ONE full ``run_halving`` re-run on the current corpus
  version, dispatched through the same cached
  :func:`~repro.engine.programs.ragged_program` as every other ragged
  tenant (the re-run key is ``fold_in(key(seed), version)``, so a
  from-scratch ``find_medoids_ragged`` on this version's snapshot with the
  same seed is **bit-identical** — pinned by ``tests/test_serve.py``).

With budgets in the exact regime (``budget_per_arm >= n_bucket *
ceil(log2 n_bucket)`` — the regime the generous-budget serving tests
already use), every served answer equals the exact medoid of the current
corpus version on generic-position data; under exact ties or near-ties
within float32 accumulation residue the served point is an eps-exact
medoid (see the precision caveat in :mod:`repro.serve.corpus`). The
store's centralities make the incumbent check itself budget-independent.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.bucketing import bucket_n
from repro.core.corr_sh import ragged_medoids
from repro.engine import round_schedule, stop_round
from repro.serve.corpus import CorpusStore


@dataclasses.dataclass(frozen=True)
class MedoidUpdate:
    """What one mutation did to the maintained answer."""
    version: int               # corpus version after the mutation
    medoid_slot: Optional[int]  # served incumbent (None: empty corpus)
    reran: bool                # True: full bandit re-run; False: O(n) keep
    pulls: int                 # distance evals charged to this mutation
    reason: str                # kept | challenger | deleted_incumbent |
    #                            bootstrap | emptied


class MaintainedMedoid:
    """The maintained medoid of a live :class:`CorpusStore`.

    ``query()`` is free — the incumbent slot id is host state. Mutations go
    through :meth:`insert` / :meth:`delete`, which mutate the store and
    re-establish the incumbent per the protocol above. All pull accounting
    is exact and split incremental-vs-re-run, so benchmarks can report the
    maintenance ratio directly.
    """

    def __init__(self, store: Optional[CorpusStore] = None, *,
                 d: Optional[int] = None, metric: str = "l2",
                 backend: str = "reference", budget_per_arm: int = 24,
                 min_bucket: Optional[int] = None, seed: int = 0):
        if store is None:
            if d is None:
                raise ValueError("pass a CorpusStore or d= to build one")
            store = CorpusStore(d, metric=metric, backend=backend,
                                **({} if min_bucket is None
                                   else {"min_bucket": min_bucket}))
        self.store = store
        self.budget_per_arm = int(budget_per_arm)
        self._key = jax.random.key(seed)
        self.medoid_slot: Optional[int] = None
        self.reruns = 0
        self.kept = 0
        self.queries = 0
        self.incremental_pulls = 0     # n-vector re-verification cost
        self.rerun_pulls = 0           # scheduled pulls of full re-runs
        if store.n:
            # adopting a pre-populated store: the incumbent must come from
            # the same protocol a mutation-triggered re-run uses
            self._rerun()

    # ------------------------------- queries -------------------------------
    def query(self) -> tuple[Optional[int], int]:
        """Serve the maintained answer: ``(medoid slot id, corpus version)``.
        No device work — the incumbent is re-established at mutation time."""
        self.queries += 1
        return self.medoid_slot, self.store.version

    @property
    def pulls(self) -> int:
        """Total distance evaluations (bootstrap + mutations + re-runs)."""
        return (self.store.init_pulls + self.incremental_pulls
                + self.rerun_pulls)

    # ------------------------------ mutations ------------------------------
    def insert(self, x) -> MedoidUpdate:
        """Insert one point; re-verify (and only if dethroned, re-run)."""
        self.store.insert(x)
        return self._settle(deleted_incumbent=False)

    def delete(self, slot: int) -> MedoidUpdate:
        """Delete a live slot; a deleted incumbent always forces a re-run."""
        was_incumbent = slot == self.medoid_slot
        self.store.delete(slot)
        return self._settle(deleted_incumbent=was_incumbent)

    def _settle(self, *, deleted_incumbent: bool) -> MedoidUpdate:
        store = self.store
        pulls = store.capacity          # the mutation's exact n-vector
        self.incremental_pulls += pulls
        if store.n == 0:
            self.medoid_slot = None
            return MedoidUpdate(store.version, None, False, pulls, "emptied")
        if deleted_incumbent:
            reason = "deleted_incumbent"
        elif self.medoid_slot is None:
            reason = "bootstrap"
        elif store.exact_medoid_slot != self.medoid_slot:
            # a challenger's exact centrality beat the incumbent's — the
            # one case the single n-vector cannot settle in the bandit's
            # favor
            reason = "challenger"
        else:
            self.kept += 1
            return MedoidUpdate(store.version, self.medoid_slot, False,
                                pulls, "kept")
        rerun_pulls = self._rerun()
        return MedoidUpdate(store.version, self.medoid_slot, True,
                            pulls + rerun_pulls, reason)

    def _rerun(self) -> int:
        """Full correlated-SH re-run on the current corpus version (the
        same cached ragged program every other tenant dispatches; key =
        ``fold_in(key(seed), version)`` so the answer is reproducible from
        the version alone). Returns its scheduled pull cost."""
        store = self.store
        n = store.n
        order = store.live_slots()
        n_bucket = bucket_n(n, store.min_bucket)
        budget = self.budget_per_arm * n_bucket
        snap = store.gather(n_bucket)
        key = jax.random.fold_in(self._key, store.version)
        meds = ragged_medoids(snap[None], jnp.asarray([n], jnp.int32), key,
                              budget=budget, metric=store.metric,
                              backend=store.backend,
                              min_bucket=store.min_bucket)
        self.medoid_slot = int(order[int(meds[0])])
        rounds = round_schedule(n_bucket, budget)
        pulls = sum(r.pulls for r in rounds[: stop_round(rounds) + 1]) \
            if rounds else 0
        self.rerun_pulls += pulls
        self.reruns += 1
        return pulls

    # -------------------------------- stats --------------------------------
    def stats(self) -> dict:
        s = self.store.stats()
        mutations = s.inserts + s.deletes
        return {
            "n": s.n, "capacity": s.capacity, "version": s.version,
            "mutations": mutations, "kept": self.kept,
            "reruns": self.reruns, "queries": self.queries,
            "grows": s.grows,
            "incremental_pulls": self.incremental_pulls,
            "rerun_pulls": self.rerun_pulls,
            "init_pulls": s.init_pulls,
            "total_pulls": self.pulls,
            "medoid_slot": self.medoid_slot,
            "kept_frac": round(self.kept / mutations, 4) if mutations else 0.0,
        }
