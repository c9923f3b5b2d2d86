"""Entry: ``repro.api.find_medoid`` on one resident corpus, one query per call.

Set-up draws the corpus on the device from the seed. Each call asks for
the medoid with a fresh key and returns when the index is on the host.
The check computes the corpus's exact centralities once with the plain
reference and compares every answer of the window with them.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import counts, reference

WARM_CALL = -1


class Entry:
    def __init__(self, *, config: dict, traffic: dict, generator, key, spans):
        from repro.api import MedoidConfig

        self.config = config
        self.n, self.d = int(config["n"]), int(config["d"])
        self.data = generator.generate(jax.random.fold_in(key, 0), (self.n,),
                                       self.d, **config.get("generator_args",
                                                            {}))[0]
        self.qkey = jax.random.fold_in(key, 1)
        self.cfg = MedoidConfig(metric=config["metric"],
                                backend=config["backend"],
                                budget_per_arm=int(config["budget_per_arm"]))
        self.spans = spans
        self.work = counts.work(self.n, self.d,
                                self.cfg.budget_per_arm * self.n)

    def warm(self) -> None:
        jax.block_until_ready(self.data)
        self.call(WARM_CALL)

    def call(self, i: int) -> dict:
        from repro.api import find_medoid

        key = jax.random.fold_in(self.qkey, i & 0x7FFFFFFF)
        with self.spans("find_medoid"):
            res = find_medoid(self.data, key, config=self.cfg)
        return {"medoid": res.medoid}

    def counters(self) -> dict:
        return {}

    def release(self) -> None:
        pass

    def check(self, run: dict) -> dict:
        """Compared number: the share of answers that are not the exact
        medoid (a tie in exact centrality counts as the medoid)."""
        theta = np.asarray(reference.centrality(
            self.data, np.int32(self.n), metric=self.config["metric"]))
        answers = [r["answer"]["medoid"] for r in run["records"]
                   if r["answer"] is not None]
        if not answers:
            return {"answered": {"value": 1.0, "limit": 0.0}}
        wrong = int(sum(theta[m] > theta.min() for m in answers))
        return {"miss_share": {"value": wrong / len(answers),
                               "limit": self.config["correct"]["miss_share"]}}
