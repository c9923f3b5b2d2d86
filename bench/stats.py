"""Small statistics shared by the metric readers."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    """The q-th percentile (linear interpolation); None with no values."""
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


def histogram_mean(counters, family: str, **labels):
    """Mean of the observations a server histogram gained between two
    snapshots ``(before, after)`` of ``MedoidServer.metrics()``, over the
    series whose labels include ``labels``; None with no observation."""
    total, count = 0.0, 0
    for sign, snap in ((-1, counters[0]), (1, counters[1])):
        for series in snap["metrics"].get(family, {}).get("series", []):
            if all(series["labels"].get(k) == v for k, v in labels.items()):
                total += sign * series["sum"]
                count += sign * series["count"]
    return total / count if count > 0 else None
