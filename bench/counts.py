"""The work a medoid query needs, from the paper's round schedule alone.

The benchmark's own copy of the schedule arithmetic of correlated
sequential halving (Baharav & Tse, NeurIPS 2019, Algorithm 1): with n arms
and a budget of B distance evaluations, round r keeps s_r arms (halving
each round) and scores each against t_r = clip(B // (s_r * ceil(log2 n)),
1, n) shared references. The query answers at the first round whose
references cover all n points or that holds two arms or fewer.

The counts are the scheduled pulls, never the padded or banded work of an
implementation, so a kernel's roofline share reads the same work whatever
implements it. Least bytes assume each round reads its s_r arms and its
t_r references from memory once, as float32 rows of width d.
"""
from __future__ import annotations

import math

F32_BYTES = 4


def rounds(n: int, budget: int) -> list[tuple[int, int]]:
    """(s_r, t_r) of every round the query runs, the output round last."""
    if n < 2:
        return []
    log2n = max(1, math.ceil(math.log2(n)))
    out, s = [], n
    for _ in range(log2n):
        t = min(max(budget // (s * log2n), 1), n)
        out.append((s, t))
        if t >= n or s <= 2:
            break
        s = math.ceil(s / 2)
    return out


def work(n: int, d: int, budget: int) -> dict:
    """Scheduled pulls, |x - y| terms (l1) or multiply-adds (Gram metrics),
    and the least bytes one query of (n, d, budget) reads."""
    rs = rounds(n, budget)
    pulls = sum(s * t for s, t in rs)
    return {"pulls": pulls,
            "terms": pulls * d,
            "bytes": sum(s + t for s, t in rs) * d * F32_BYTES}
