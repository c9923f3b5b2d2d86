"""The cosine pipeline on Netflix-like ratings rows.

* the fused Gram centrality kernel under cosine, interpreted, against the
  plain blocked distances at the round loop's lopsided call shapes;
* ``find_medoid(metric="cosine", backend="pallas_fused")`` against the
  exact medoid on seeded keys;
* the work odometer's ``normed`` tally: the operand rows whose norms the
  centrality calls take, equal to a hand count of the banded schedule.
"""
import jax
import numpy as np
import pytest

from repro.api import find_medoid, find_medoids_batch
from repro.core import distances
from repro.core.exact import exact_medoid
from repro.data.medoid_datasets import netflix_like
from repro.engine import instrument, round_schedule
from repro.engine.schedule import as_schedule
from repro.kernels import ops as kops

D = 300            # not a multiple of the kernel's 256-wide width tile


# A scan band's candidates against a few references, a late round's few
# candidates against many, and a middle round.
@pytest.mark.parametrize("c,r", [(2, 600), (600, 2), (8, 130)])
@pytest.mark.parametrize("seed", [0, 1])
def test_cosine_kernel_matches_plain_distances(c, r, seed):
    rows = netflix_like(jax.random.key(seed), c + r, D)
    x, y = rows[:c], rows[c:]
    got = np.asarray(kops.kernel_centrality_sums(x, y, metric="cosine",
                                                 interpret=True))
    want = np.asarray(distances.centrality_sums(x, y, "cosine"))
    assert got.shape == (c,)
    # Each sum adds r cosine distances in [0, 2]. Both paths normalise the
    # rows and take the dot in float32, rounding in different orders; a
    # term differs by at most 1.5e-7 (about one ulp of 1) on these rows,
    # so the sums agree within r x 1e-6.
    np.testing.assert_allclose(got, want, rtol=0, atol=r * 1e-6)


@pytest.mark.parametrize("data_seed", [0, 1])
def test_find_medoid_cosine_is_the_exact_medoid(data_seed):
    x = netflix_like(jax.random.key(data_seed), 512, D)
    want = int(exact_medoid(x, "cosine"))
    for key in range(3):
        res = find_medoid(x, jax.random.key(key), metric="cosine",
                          backend="pallas_fused", budget_per_arm=30)
        assert res.medoid == want, (data_seed, key)


# n = 512, 30 pulls per arm: scan bands of width 512, 64 and 8 against
# reference buffers of 13, 106 and 426 (3, 3 and 2 trips), then the output
# round's 2 arms x 512 references.
BLOCKS = [(512, 13, 3), (64, 106, 3), (8, 426, 2), (2, 512, 1)]
NORMED = sum(trips * (rows + refs) for rows, refs, trips in BLOCKS)


def test_hand_count_is_the_schedules():
    sched = as_schedule(round_schedule(512, 30 * 512))
    stk = sched.stacked(512)
    blocks = [(b.width, b.ref_cap, len(b)) for b in stk.bands]
    blocks.append((stk.sizes[stk.r_stop], sched[stk.r_stop].num_refs, 1))
    assert blocks == BLOCKS
    assert NORMED == 3467


@pytest.mark.parametrize("metric,backend,normed", [
    ("cosine", "pallas_fused", NORMED),
    ("cosine", "reference", NORMED),
    ("l2", "pallas_fused", NORMED),
    ("l1", "pallas_fused", 0),
])
def test_normed_tally_matches_a_hand_count(metric, backend, normed):
    x = netflix_like(jax.random.key(2), 512, D)
    with instrument.deltas() as d:
        for key in range(2):
            find_medoid(x, jax.random.key(key), metric=metric,
                        backend=backend, budget_per_arm=30)
    work = d.work("medoid")
    assert work.normed == 2 * normed
    # the Gram metrics gather the rows they norm; l1 on the fused backend
    # fetches its rows by index and gathers none
    assert work.gathered == 2 * normed
    counters = instrument.work_counters()
    assert set(counters) == {"called", "computed", "normed", "gathered"}


def test_normed_tally_counts_every_query_of_a_batch():
    x = netflix_like(jax.random.key(3), 512, D)
    with instrument.deltas() as d:
        find_medoids_batch(np.stack([x, x[::-1]]), jax.random.key(0),
                           metric="cosine", backend="pallas_fused",
                           budget_per_arm=30)
    assert d.work("batch").normed == 2 * NORMED
