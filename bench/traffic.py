"""The one traffic generator: a mix file in ``bench/traffic/`` is data.

Two kinds of mix, both read from the same keys by :func:`plan`:

``"kind": "batch"`` - bulk retrieval by one caller. ``batch`` queries per
call at ``k``; the pool holds ``pool_batches`` stratified chunks of
``batch`` queries each (one chunk is one call), which the window cycles
through in order.

``"kind": "open_loop"`` - independent users. ``rate_qps`` arrivals per
second for the whole window, each one query at ``k``, sent on schedule
whether or not earlier ones have finished. The gaps are exponential
(Poisson arrivals), drawn by stratified inverse CDF so every seed sends
the same number of requests with the same gap distribution in another
order. Queries come in stratified chunks of ``chunk`` in arrival order. ``max_batch`` and
``batch_deadline_ms`` are the frontend's batching knobs.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .corpus import ARRIVALS, Shape, make_queries, rng_for, stratified


@dataclass
class Plan:
    kind: str
    k: int
    queries: list                 # the pool, in the order it is sent
    batch: int = 0                # batch: queries per call
    due_s: np.ndarray | None = None   # open_loop: due time of each query


def arrival_times(rate_qps: float, seconds: float,
                  seed: int) -> np.ndarray:
    """Due times in ``[0, seconds)`` of ``round(rate_qps * seconds)``
    open-loop arrivals (see module docstring)."""
    n = max(1, int(round(rate_qps * seconds)))
    rng = rng_for(seed, ARRIVALS)
    gaps = -np.log1p(-stratified(rng, n))        # Exp(1), stratified
    s = np.cumsum(gaps)
    s *= n / (s[-1] + gaps.mean())                # n arrivals over n units
    return s * (seconds / n)


def plan(traffic: dict, shape: Shape, seed: int, seconds: float) -> Plan:
    kind = traffic["kind"]
    k = int(traffic["k"])
    if kind == "batch":
        b = int(traffic["batch"])
        qs = make_queries(shape, seed, n_chunks=int(traffic["pool_batches"]),
                          chunk=b)
        return Plan(kind, k, qs, batch=b)
    if kind == "open_loop":
        due = arrival_times(float(traffic["rate_qps"]), seconds, seed)
        chunk = int(traffic["chunk"])
        qs = make_queries(shape, seed, n_chunks=-(-due.size // chunk),
                          chunk=chunk)[:due.size]
        return Plan(kind, k, qs, due_s=due)
    raise ValueError(f"unknown traffic kind {kind!r}")
