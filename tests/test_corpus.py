"""The id-level Zipf corpus generator behind the deployment-size runs."""

import numpy as np

from repro.data.corpus import zipf_corpus


def test_zipf_corpus_lengths_range_and_determinism():
    a = zipf_corpus(500, 1000, avg_len=30, seed=3)
    assert len(a) == 500
    lens = np.array([d.size for d in a])
    assert lens.min() >= 1 and abs(lens.mean() - 30) < 2
    flat = np.concatenate(a)
    assert flat.dtype == np.int32
    assert flat.min() >= 0 and flat.max() < 1000
    assert np.bincount(flat).argmax() == 0          # Zipf head is token 0
    b = zipf_corpus(500, 1000, avg_len=30, seed=3)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    c = zipf_corpus(500, 1000, avg_len=30, seed=4)
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))


def test_zipf_corpus_matches_per_document_draws():
    """One vectorized draw reproduces the per-document ``rng.choice``
    stream token for token (the seed's generator), so corpora built from
    a seed are unchanged."""
    n_docs, n_vocab, seed = 40, 300, 7
    rng = np.random.default_rng(seed)
    p = np.arange(1, n_vocab + 1, dtype=np.float64) ** -1.07
    p /= p.sum()
    lens = np.maximum(1, rng.poisson(12, size=n_docs))
    ref = [rng.choice(n_vocab, size=int(n), p=p) for n in lens]
    got = zipf_corpus(n_docs, n_vocab, avg_len=12, seed=seed)
    assert all(np.array_equal(x, y) for x, y in zip(ref, got))
