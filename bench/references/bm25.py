"""Plain BM25 straight from a corpus, independent of the system under test.

Scores follow Lucene's BM25 as published (Kamphuis et al., "Which BM25
Do You Mean?", ECIR 2020; bm25s's ``method="lucene"``, the method every
configuration states), computed from the raw content tokens of the
benchmark's own corpus: document frequencies, document lengths and term
frequencies are counted here, so the eager index build of the system
under test is checked and not trusted. Nothing of the program is
imported.

Only the postings of the terms that the checked queries use are counted,
so one pass over the corpus tokens serves a whole sample of queries.
``dtype`` is the arithmetic of every step (idf, length norm, term
weight, per-document sum): float64 for the reference, a lower precision
for the control that has to fail the comparison.
"""

from __future__ import annotations

import numpy as np


class BM25Reference:
    """Exact scores of every document for queries over ``terms``."""

    def __init__(self, tokens: np.ndarray, offsets: np.ndarray,
                 terms: np.ndarray, *, method: str, k1: float, b: float,
                 dtype=np.float64):
        if method != "lucene":
            raise ValueError(f"no reference for BM25 method {method!r}")
        n_docs = offsets.size - 1
        self.n_docs = n_docs
        self.dtype = dtype
        terms = np.unique(np.asarray(terms, np.int64))
        pos = np.flatnonzero(np.isin(tokens, terms.astype(tokens.dtype)))
        doc = np.searchsorted(offsets, pos, side="right") - 1
        key, tf = np.unique(tokens[pos].astype(np.int64) * n_docs + doc,
                            return_counts=True)
        term, doc = key // n_docs, key % n_docs
        bounds = np.searchsorted(term, terms)
        bounds = np.append(bounds, term.size)
        # every step below in ``dtype``: idf, length norm, term weight
        one, half = dtype(1.0), dtype(0.5)
        dl = np.diff(offsets)
        avg = dtype(dl.mean())
        norm = dtype(k1) * (one - dtype(b) + dtype(b) * dl.astype(dtype)
                            / avg)
        df = np.maximum(np.diff(bounds), 1).astype(dtype)
        n = dtype(n_docs)
        idf = np.log(one + (n - df + half) / (df + half))    # lucene
        tf = tf.astype(dtype)
        weight = np.repeat(idf, np.diff(bounds)) * tf / (tf + norm[doc])
        self._postings = {int(t): (doc[lo:hi], weight[lo:hi])
                          for t, lo, hi in zip(terms, bounds[:-1],
                                               bounds[1:])}

    def scores(self, query: np.ndarray) -> np.ndarray:
        """``[n_docs]`` scores in ``dtype``; a repeated query token counts
        once per occurrence."""
        s = np.zeros(self.n_docs, self.dtype)
        for t in np.asarray(query).ravel().tolist():
            d, w = self._postings[int(t)]
            s[d] += w
        return s

    def top_k(self, query: np.ndarray, k: int
              ) -> tuple[np.ndarray, np.ndarray]:
        """The ``k`` best documents, best first (ties in id order)."""
        s = self.scores(query)
        k = min(k, s.size)
        part = np.argpartition(-s.astype(np.float64), k - 1)[:k]
        order = np.lexsort((part, -s[part].astype(np.float64)))
        ids = part[order]
        return ids, s[ids]
