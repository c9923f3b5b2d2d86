"""Breaks planted under the timed path, to show that ``correct`` catches them.

Each break replaces a function of the program, through its module's
namespace, for the rest of the process:

* ``program_bf16`` -- the program's own bfloat16 path switched on
  (``precision="bf16"`` in ``find_medoid``'s config and in the server),
  with the float32 verification that path carries;
* ``bf16`` -- the plain reference computed a precision step lower
  (``reference.py``'s bfloat16 path) answers in the program's place;
* ``runner_up`` -- the plain reference's second-best point answers: the
  nearest miss of the exact medoid;
* ``answer_altered`` -- every answer is moved to the next index where it
  is produced;
* ``half_batch`` -- a dispatch answers only the first half of the real
  requests of its batch; the rest get index 0 (serving only).

``python bench/control.py`` runs a cell with one of them on the chip; the
benchmark's own runs never plant any.
"""
from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp

BREAKS = ("program_bf16", "bf16", "runner_up", "answer_altered",
          "half_batch")
CONTROLS = ("bf16", "runner_up")


def _control(name, data, count, metric):
    from bench import reference

    precision = "bf16" if name == "bf16" else "fp32"
    theta = reference.centrality(data, count, metric=metric,
                                 precision=precision)
    if name == "runner_up":
        theta = theta.at[jnp.argmin(theta)].set(jnp.inf)
    return jnp.argmin(theta)


def _plant_program_bf16(api, serve_medoid) -> None:
    find = api.find_medoid

    def bf16_find(data, key=None, *, config=None, **kw):
        cfg = config if config is not None else api.MedoidConfig(**kw)
        return find(data, key, config=dataclasses.replace(cfg,
                                                          precision="bf16"))

    api.find_medoid = bf16_find
    serve_medoid.MedoidServer = functools.partial(serve_medoid.MedoidServer,
                                                  precision="bf16")


def plant(name: str) -> None:
    """Plant break ``name`` in both entry points the benchmark drives."""
    if name not in BREAKS:
        raise ValueError(f"unknown break {name!r}; one of {BREAKS}")
    from repro import api
    from repro.launch import serve_medoid

    if name == "program_bf16":
        _plant_program_bf16(api, serve_medoid)
        return
    find = api.find_medoid
    ragged = serve_medoid.ragged_medoids

    def broken_find(data, key=None, *, config=None, **kw):
        res = find(data, key, config=config, **kw)
        cfg = config if config is not None else api.MedoidConfig(**kw)
        if name in CONTROLS:
            medoid = int(_control(name, data, jnp.int32(res.n), cfg.metric))
        elif name == "answer_altered":
            medoid = (res.medoid + 1) % res.n
        else:
            return res
        return dataclasses.replace(res, medoid=medoid)

    def broken_ragged(data, lengths, key, **kw):
        out = ragged(data, lengths, key, **kw)
        with_tel = kw.get("telemetry", False)
        medoids, tel = out if with_tel else (out, None)
        if name in CONTROLS:
            medoids = jnp.stack([
                _control(name, data[b], lengths[b], kw["metric"])
                for b in range(data.shape[0])]).astype(medoids.dtype)
        elif name == "answer_altered":
            medoids = (medoids + 1) % lengths
        else:
            real = jnp.sum(lengths > 1)
            keep = jnp.arange(medoids.shape[0]) < real - real // 2
            medoids = jnp.where(keep, medoids, 0)
        return (medoids, tel) if with_tel else medoids

    api.find_medoid = broken_find
    serve_medoid.ragged_medoids = broken_ragged
