"""How ``correct`` is decided: served boards against the plain reference.

A sample of the answers the window produced, drawn from the seed with the
heaviest queries always in it, is scored again by the configuration's
reference (``bench/references/<name>.py``) in float64, straight from the
corpus. Three numbers are compared, each with its own limit:

* ``score_err`` - the widest gap, over every sampled query and rank, of
  (a) the served score against the reference's score at that rank and
  (b) the served score against the reference's score of the served
  document, over the query's best reference score. Ties may order either
  way; a wrong document, a wrong score or a lost posting shows in one of
  the two. Limit: from the configuration file (``limits``).
* ``invalid`` - boards of the wrong shape, with a document twice, an id
  out of range or a score that is not finite. Limit 0.
* ``unanswered`` - sampled requests whose answer never came. Limit 0.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass

import numpy as np

from .corpus import SAMPLE, Corpus, rng_for


@dataclass
class Answer:
    query: np.ndarray
    ids: np.ndarray | None        # None: the answer never came
    scores: np.ndarray | None


def sample_answers(answers: list[Answer], work: np.ndarray, n: int,
                   seed: int) -> list[Answer]:
    """``n`` answers: the ``n // 8`` with the most posting work, the rest
    drawn from the seed."""
    if len(answers) <= n:
        return list(answers)
    heavy = np.argsort(-np.asarray(work), kind="stable")[:n // 8]
    rest = np.setdiff1d(np.arange(len(answers)), heavy)
    pick = rng_for(seed, SAMPLE).choice(rest, n - heavy.size, replace=False)
    return [answers[i] for i in np.concatenate([heavy, np.sort(pick)])]


def reference_for(cfg: dict, corpus: Corpus, queries, dtype=np.float64):
    mod = importlib.import_module(f"bench.references.{cfg['reference']}")
    terms = np.unique(np.concatenate([np.asarray(q).ravel()
                                      for q in queries]))
    return mod.BM25Reference(corpus.tokens, corpus.offsets, terms,
                             method=cfg["method"], k1=float(cfg["k1"]),
                             b=float(cfg["b"]), dtype=dtype)


def board_error(ref, query, ids, scores, k: int) -> tuple[float, bool]:
    """(relative score gap, invalid) of one served board."""
    s = ref.scores(query).astype(np.float64)
    kk = min(k, s.size)
    ids = np.asarray(ids)
    got = np.asarray(scores, np.float64)
    if (ids.shape != (kk,) or got.shape != (kk,)
            or not np.isfinite(got).all()
            or ids.min(initial=0) < 0 or ids.max(initial=0) >= s.size
            or np.unique(ids).size != kk):
        return 0.0, True
    best = -np.sort(np.partition(-s, kk - 1)[:kk])
    scale = best[0] if best[0] > 0 else 1.0
    gap = max(np.abs(got - best).max(), np.abs(got - s[ids]).max())
    return float(gap / scale), False


def compare(cfg: dict, corpus: Corpus, answers: list[Answer], k: int
            ) -> dict:
    """The compared numbers of one run, each beside its limit."""
    unanswered = sum(a.ids is None for a in answers)
    came = [a for a in answers if a.ids is not None]
    err, invalid = 0.0, 0
    if came:
        ref = reference_for(cfg, corpus, [a.query for a in came])
        for a in came:
            e, bad = board_error(ref, a.query, a.ids, a.scores, k)
            err, invalid = max(err, e), invalid + bad
    return {"score_err": {"value": err,
                          "limit": float(cfg["limits"]["score_err"])},
            "invalid": {"value": invalid, "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0}}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
