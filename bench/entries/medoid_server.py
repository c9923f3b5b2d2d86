"""Entry: ``repro.launch.serve_medoid.MedoidServer``, fed one request at a time.

Set-up draws a pool of point sets on the device from the seed: as many as
the traffic mix's ``pool``, each of the configuration's ``n`` rows. It
builds the server as the configuration says and warms it by serving every
set of the pool once: that compiles the dispatch program of the sets'
size bucket and their packing, and nothing else. In the window each
request asks for the medoid of one set of the pool.

The check computes each set's exact medoid once with the plain reference
and compares every answer of the run with it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, reference


def next_pow2(n: int) -> int:
    return 1 << (int(n) - 1).bit_length()


class Entry:
    def __init__(self, *, config: dict, traffic: dict, generator, key, spans):
        from repro.launch import serve_medoid

        self.config = config
        self.d = int(config["d"])
        self.sizes = [int(config["n"])] * int(traffic["pool"])
        self.pool_size = len(self.sizes)
        self.sets = generator.generate(key, self.sizes, self.d,
                                       **config.get("generator_args", {}))
        spans.wrap(serve_medoid, "pack_queries", "pack")
        spans.wrap(serve_medoid, "ragged_medoids", "dispatch")
        spans.wrap(serve_medoid, "telemetry_to_host", "telemetry_pull")
        self.server = serve_medoid.MedoidServer(**config["server"])
        self.budget_per_arm = self.server.budget_per_arm
        self.request = {}            # server rid -> request index
        self.work = [counts.work(n, self.d, self.budget_per_arm * n)
                     for n in self.sizes]
        self.served = []             # (request index, set) in answer order

    def warm(self) -> None:
        jax.block_until_ready(self.sets)
        for s in self.sets:
            self.server.submit(s)
        self.server.drain()
        self.server.done.clear()

    @property
    def pending(self) -> int:
        return self.server.pending

    def submit(self, i: int, s: int) -> None:
        rid = self.server.submit(self.sets[s])
        self.request[rid] = (i, s)

    def step(self) -> list:
        out = []
        for q in self.server.step():
            i, s = self.request.pop(q.rid)
            self.server.done.pop(q.rid, None)
            out.append((i, {"medoid": q.medoid, "gap": q.gap}))
            self.served.append((i, s))
        return out

    def counters(self) -> dict:
        return {"metrics": self.server.metrics(),
                "dispatches": self.server.dispatches,
                "served": len(self.served)}

    def release(self) -> None:
        self.server = None

    def check(self, run: dict) -> dict:
        """Compared number: the share of answers that are not their set's
        exact medoid."""
        rows = next_pow2(max(self.sizes))
        flat = jnp.concatenate(self.sets + [jnp.zeros((rows, self.d),
                                                      jnp.float32)])
        offsets = np.concatenate([[0], np.cumsum(self.sizes)[:-1]])
        exact = []
        for s, n in enumerate(self.sizes):
            i, _ = reference.slice_medoid(
                flat, np.int32(offsets[s]), np.int32(n), rows=next_pow2(n),
                metric=self.config["server"]["metric"])
            exact.append(i)
        exact = [int(i) for i in exact]
        answered = [r for r in run["records"] if r["answer"] is not None]
        if not answered:
            return {"answered": {"value": 1.0, "limit": 0.0}}
        wrong = sum(r["answer"]["medoid"] != exact[r["set"]]
                    for r in answered)
        return {"miss_share": {"value": wrong / len(answered),
                               "limit": self.config["correct"]["miss_share"]}}
