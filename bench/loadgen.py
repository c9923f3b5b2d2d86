"""The one load generator: reads a traffic mix and drives an entry with it.

A traffic mix is a JSON file of parameters under ``bench/traffic/``:

* ``"loop": "closed"`` -- one caller, sending its next query when the
  last one has answered. Every query is due when it is sent.
* ``"loop": "open"`` -- requests arrive on a schedule fixed before the
  window, whether or not earlier ones have answered, at ``rate_per_s``.
  ``arrivals: "poisson"`` spaces them by exponential gaps. Every seed gets
  the same multiset of gaps (the exponential's quantiles) in its own
  order, so the work of a run does not change with the seed. Each request
  names one point set of the entry's pool; every pass over the pool takes
  each set once, in an order drawn from the seed.

Each request's record holds its ``due``, ``start`` and ``finish`` times on
the host clock (``time.perf_counter``), and its ``answer``.
"""
from __future__ import annotations

import math
import time

import numpy as np

clock = time.perf_counter


def arrival_offsets(traffic: dict, seconds: float, rng) -> np.ndarray:
    """Due times, in seconds from the window's start, of every request due
    inside a window of ``seconds``."""
    if traffic.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    rate = float(traffic["rate_per_s"])
    count = max(1, int(round(rate * seconds)))
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate
    due = np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])
    return due[due < seconds]


def pool_order(count: int, pool: int, rng) -> np.ndarray:
    """The pool set each of ``count`` requests asks about."""
    passes = [rng.permutation(pool) for _ in range(-(-count // pool))]
    return np.concatenate(passes)[:count]


def closed_loop(entry, seconds: float, spans) -> dict:
    """One caller: query after query until ``seconds`` have passed. The
    window ends when the last query ends."""
    records = []
    t0 = clock()
    end = t0 + seconds
    i = 0
    with spans("window"):
        while True:
            start = clock()
            if start >= end:
                break
            with spans("call"):
                answer = entry.call(i)
            records.append({"i": i, "due": start, "start": start,
                            "finish": clock(), "answer": answer})
            i += 1
    t_end = records[-1]["finish"] if records else clock()
    return {"records": records, "t0": t0, "t_end": t_end,
            "window_s": t_end - t0, "late_s": 0.0}


def open_loop(entry, traffic: dict, seconds: float, rng, spans) -> dict:
    """Requests submitted as they fall due; the server stepped whenever it
    holds work. The window closes ``seconds`` after the first due time
    (a step under way then runs to its end); a request still open then
    counts with its age at the close (see ``finish_open``)."""
    offsets = arrival_offsets(traffic, seconds, rng)
    sets = pool_order(len(offsets), entry.pool_size, rng)
    records = [{"i": i, "set": int(s), "due": None, "start": None,
                "finish": None, "answer": None}
               for i, s in enumerate(sets)]
    late = 0.0
    longest = (0.0, 0.0)         # (seconds, start offset) of the longest step
    nxt = 0
    t0 = clock()
    end = t0 + seconds
    due = t0 + offsets

    def collect(done):
        now = clock()
        for i, answer in done:
            records[i]["finish"], records[i]["answer"] = now, answer

    with spans("window"):
        while True:
            now = clock()
            if now >= end:
                break
            while nxt < len(records) and due[nxt] <= now:
                rec = records[nxt]
                rec["due"], rec["start"] = float(due[nxt]), now
                late = max(late, now - rec["due"])
                with spans("submit"):
                    entry.submit(nxt, rec["set"])
                nxt += 1
            if entry.pending:
                t = clock()
                with spans("step"):
                    collect(entry.step())
                longest = max(longest, (clock() - t, t - t0))
            else:
                wake = min(due[nxt] if nxt < len(records) else end, end)
                with spans("wait_arrival"):
                    time.sleep(max(0.0, wake - clock()))
    return {"records": records, "t0": t0, "t_end": end, "window_s": seconds,
            "late_s": late, "longest_step": longest, "due": due,
            "submitted": nxt}


def finish_open(entry, run: dict, grace_s: float = 60.0) -> None:
    """After the close: submit the requests due in the window that were not
    yet submitted, and answer every open one, for the check. Waits at most
    ``grace_s`` plus the window's length."""
    now = clock()
    for rec in run["records"][run["submitted"]:]:
        rec["due"], rec["start"] = float(run["due"][rec["i"]]), now
        entry.submit(rec["i"], rec["set"])
    deadline = now + grace_s + run["window_s"]
    while entry.pending and clock() < deadline:
        done = entry.step()
        t = clock()
        for i, answer in done:
            run["records"][i]["finish"] = t
            run["records"][i]["answer"] = answer


def latency_ms(run: dict) -> list[float]:
    """Each due request's time from due to answer, in ms; one still open at
    the window's close counts with its age then."""
    out = []
    for r in run["records"]:
        fin = r["finish"] if r["finish"] is not None else math.inf
        out.append((min(fin, run["t_end"]) - r["due"]) * 1e3)
    return out
