"""Seeded corpora and query pools at a BEIR deployment's published shapes.

Nothing can be downloaded, so a configuration file (``bench/configs/``)
states the deployment's published sizes (documents, mean words per
document and per query) and, under ``assumed``, what the source does not
give: the vocabulary's rank count, the Zipf exponent of word frequencies,
how many of the most frequent ranks an English stopword list strips, and
the law query terms follow (``query_term_law``: ``"document"``, the one
law the generator has).

Words follow one Zipf law over ``vocab_ranks`` ranks. The ``stopword_ranks``
most frequent ranks are stopwords, which bm25s removes before indexing
(``stopwords="en"``), so a document or query keeps only its content
tokens. A Poisson number of words with each word independently a
stopword leaves a Poisson number of content tokens (thinning), so each
document draws its content length from Poisson(mean words x content
share) and its tokens from the content part of the law. Content token ids
are ``rank - stopword_ranks - 1``, in ``[0, n_vocab)``.

The number of distinct (document, token) pairs, the index's posting
count, is then set to its expectation under the law, the same for every
seed: at the deployments' sizes the realised count misses it by about
0.01%, and that many documents each swap one token (a repeated token for
one they lack, or a token they hold once for one they hold again;
lengths stay). The device
index's arrays are sized from the posting count, so every seed then runs
the same compiled programs, and set-up finds them all in the cache.

Query terms follow the same content law as document words, so a term is
drawn as often as it occurs in the corpus: BEIR publishes no query-term
df, and this assumption sets each query's posting work (its Σdf).
Queries are drawn by stratified sampling, chunk by chunk: the lengths of
a chunk's queries and the ranks of all its term slots each come from one
stratified inverse-CDF draw (slot ``i`` of ``n`` draws its uniform from
``[i/n, (i+1)/n)``), shuffled. Every chunk therefore holds the same
counts of head, torso and tail terms, and the posting work of a chunk,
and of any window made of whole chunks, barely moves with the seed.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np
from scipy.special import gammaln

# streams of one seed, one per use, so that a change in how many draws one
# use makes never shifts another's
CORPUS, QUERIES, ARRIVALS, SAMPLE = range(4)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, stream])


@dataclass(frozen=True)
class Shape:
    """The generator's view of a configuration file."""

    n_docs: int
    doc_words_mean: float
    query_words_mean: float
    vocab_ranks: int
    zipf_alpha: float
    stopword_ranks: int

    @staticmethod
    def from_config(cfg: dict) -> "Shape":
        a = cfg["assumed"]
        if a.get("query_term_law") != "document":
            raise ValueError("queries follow the documents' law only "
                             "(assumed.query_term_law: \"document\")")
        return Shape(n_docs=int(cfg["n_docs"]),
                     doc_words_mean=float(cfg["doc_words_mean"]),
                     query_words_mean=float(cfg["query_words_mean"]),
                     vocab_ranks=int(a["vocab_ranks"]),
                     zipf_alpha=float(a["zipf_alpha"]),
                     stopword_ranks=int(a["stopword_ranks"]))

    @property
    def n_vocab(self) -> int:
        return self.vocab_ranks - self.stopword_ranks

    def law(self) -> tuple[float, np.ndarray]:
        """(content share of all words, CDF over content token ids)."""
        p = np.arange(1, self.vocab_ranks + 1, dtype=np.float64)
        p **= -self.zipf_alpha
        p /= p.sum()
        content = p[self.stopword_ranks:]
        cdf = np.cumsum(content)
        share = float(cdf[-1])
        return share, cdf / share


@dataclass
class Corpus:
    """Content tokens of every document, flat: document ``d`` is
    ``tokens[offsets[d]:offsets[d + 1]]``."""

    tokens: np.ndarray        # [T] int32 content token ids
    offsets: np.ndarray       # [n_docs + 1] int64
    n_vocab: int

    @property
    def n_docs(self) -> int:
        return self.offsets.size - 1

    def documents(self) -> list[np.ndarray]:
        """Per-document views (no copy), the form ``build_index`` takes."""
        return np.split(self.tokens, self.offsets[1:-1])


def make_corpus(shape: Shape, seed: int) -> Corpus:
    rng = rng_for(seed, CORPUS)
    share, cdf = shape.law()
    lens = rng.poisson(shape.doc_words_mean * share, size=shape.n_docs)
    offsets = np.zeros(shape.n_docs + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    tokens = np.empty(int(offsets[-1]), np.int32)

    def invert(lo, u):                 # searchsorted releases the GIL
        tokens[lo:lo + u.size] = cdf.searchsorted(u, side="right")

    chunk = 1 << 22
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        done = [pool.submit(invert, lo, rng.random(min(chunk,
                                                       tokens.size - lo)))
                for lo in range(0, tokens.size, chunk)]
        for f in done:
            f.result()
    np.minimum(tokens, shape.n_vocab - 1, out=tokens)   # u rounding to 1.0
    m = shape.doc_words_mean * share
    pmf = np.diff(cdf, prepend=0.0)
    target = int(round(shape.n_docs * -np.expm1(-m * pmf).sum()))
    tokens = _fix_postings(tokens, offsets, shape.n_vocab, target, cdf, rng)
    return Corpus(tokens=tokens, offsets=offsets, n_vocab=shape.n_vocab)


def _fix_postings(tokens, offsets, n_vocab, target, cdf, rng):
    """Tokens sorted within each document, with exactly ``target``
    distinct (document, token) pairs (see module docstring)."""
    n_docs = offsets.size - 1
    doc = np.repeat(np.arange(n_docs, dtype=np.int64), np.diff(offsets))
    key = doc * n_vocab + tokens
    del doc

    def sort(lo, hi):                  # per-document sorts, GIL released
        key[lo:hi].sort()

    bounds = offsets[np.linspace(0, n_docs, 17).astype(np.int64)]
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        for f in [pool.submit(sort, lo, hi)
                  for lo, hi in zip(bounds[:-1], bounds[1:])]:
            f.result()
    first = np.ones(key.size, bool)
    first[1:] = key[1:] != key[:-1]
    delta = int(np.count_nonzero(first)) - target
    if delta == 0:
        return (key % n_vocab).astype(np.int32)
    lo, hi = offsets[:-1], offsets[1:]
    if delta < 0:
        # documents holding a repeated token swap one copy for a token
        # they lack
        dup = np.flatnonzero(~first)
        at = np.searchsorted(dup, lo)
        ok = at < dup.size
        ok[ok] = dup[at[ok]] < hi[ok]
    else:
        # documents holding a token once and another token swap the one
        # for a second copy of the other
        start = np.flatnonzero(first)
        single = start[np.diff(np.append(start, key.size)) == 1]
        at = np.searchsorted(single, lo)
        ok = (at < single.size) & (hi - lo >= 2)
        ok[ok] = single[at[ok]] < hi[ok]
    docs = np.flatnonzero(ok)
    # one swap per document: at deployment sizes hundreds of thousands
    # qualify for a few thousand swaps; a tiny test corpus may fall short
    docs = np.sort(rng.choice(docs, min(abs(delta), docs.size),
                              replace=False))
    if delta < 0:
        pos = dup[np.searchsorted(dup, lo[docs])]
        new = np.full(pos.size, -1, np.int64)
        todo = np.arange(pos.size)
        while todo.size:
            t = np.minimum(cdf.searchsorted(rng.random(todo.size),
                                            side="right"), n_vocab - 1)
            k = docs[todo] * n_vocab + t
            i = np.minimum(np.searchsorted(key, k), key.size - 1)
            free = key[i] != k
            new[todo[free]] = k[free]
            todo = todo[~free]
        key[pos] = new
    else:
        pos = single[np.searchsorted(single, lo[docs])]
        # the document's first token, or its next one when that is this
        other = np.where(pos == lo[docs], pos + 1, lo[docs])
        key[pos] = key[other]
    return (key % n_vocab).astype(np.int32)


def stratified(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` uniforms, one in each of ``[i/n, (i+1)/n)``, shuffled."""
    u = (np.arange(n) + rng.random(n)) / n
    rng.shuffle(u)
    return u


def _poisson_cdf(lam: float) -> np.ndarray:
    k = np.arange(int(lam + 12 * np.sqrt(lam + 1) + 12), dtype=np.float64)
    pmf = np.exp(k * np.log(lam) - lam - gammaln(k + 1)) if lam > 0 \
        else (k == 0).astype(np.float64)
    cdf = np.cumsum(pmf)
    return cdf / cdf[-1]


def make_queries(shape: Shape, seed: int, *, n_chunks: int,
                 chunk: int) -> list[np.ndarray]:
    """``n_chunks * chunk`` queries of content token ids, stratified per
    chunk (see module docstring). A query keeps at least one content
    token: ``1 + Poisson(mean content tokens - 1)`` of them."""
    rng = rng_for(seed, QUERIES)
    share, cdf = shape.law()
    mean_tokens = max(shape.query_words_mean * share, 1.0)
    len_cdf = _poisson_cdf(mean_tokens - 1.0)
    out: list[np.ndarray] = []
    for _ in range(n_chunks):
        lens = 1 + len_cdf.searchsorted(stratified(rng, chunk),
                                        side="right")
        ids = cdf.searchsorted(stratified(rng, int(lens.sum())),
                               side="right")
        ids = np.minimum(ids, shape.n_vocab - 1).astype(np.int32)
        out.extend(np.split(ids, np.cumsum(lens)[:-1]))
    return out
