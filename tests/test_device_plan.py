"""Device-side batch planning + double-buffered fragment DMA (PR 4).

Pins the fully-device-resident serving contract:

* **sparse** — the jit-compiled fragment builder
  (``sparse.fragment_device``) emits a table BYTE-IDENTICAL to the host
  ``fragment_plan`` across head/tail/dense df profiles, empty queries and
  df-0 tokens, and turns nf-bucket overflow into a larger-bucket retry
  (never truncation); its device default-doc ids match
  ``core.retrieval.default_doc_ids``.
* **kernel** — the double-buffered DMA schedule is bit-identical to the
  single-buffer oracle on all five BM25 variants (same scatter/fold
  helpers, different copy schedule only).
* **serve** — with ``plan="device"`` the steady-state batch ships ZERO
  posting and ZERO descriptor bytes host→device (the PR's acceptance
  invariant); ``host_arrays="drop"`` serves exactly without any host CSC
  posting copy; ``last_plan`` records the plan mode.
* **core** — the planner's crossover discounts the now-free device
  descriptor build.
"""

import numpy as np
import pytest

from conftest import given, make_corpus, settings, st
from repro.core import (BM25Params, ScipyBM25, build_index,
                        build_sharded_indexes, default_doc_ids,
                        dense_oracle_scores, plan_retrieval, topk_numpy)
from repro.core.retrieval import DEFAULT_CROSSOVER, DEVICE_PLAN_DISCOUNT
from repro.serve import DeviceRetriever, RetrievalEngine
from repro.sparse.block_csr import (TRANSFERS, DeviceIndex, bucket_pow2,
                                    fragment_plan, reset_transfer_stats)
from repro.sparse.fragment_device import (build_fragment_table,
                                          plan_fragments_device)

# transfer/plan counters asserted here change legitimately when a
# chaos fault forces a ladder hop (e.g. an extra host-gather upload)
pytestmark = pytest.mark.no_chaos

ALL_VARIANTS = ["robertson", "atire", "lucene", "bm25l", "bm25+"]

SMALL = dict(block_size=16, tile=16, acc_block=16, frag=8, q_max=8)

BIG = np.iinfo(np.int32).max


def _pad_uniq(uniq: np.ndarray, floor: int = 8) -> np.ndarray:
    """uniq tokens -> the padded sentinel table ``pack_query_batch`` uses."""
    u_max = bucket_pow2(max(uniq.size, 1), floor=floor)
    tab = np.full(u_max, BIG, dtype=np.int32)
    tab[: uniq.size] = uniq
    return tab


def _profile_uniq(rng, profile: str, n_vocab: int) -> np.ndarray:
    if profile == "head":
        pool = np.arange(0, max(4, n_vocab // 8))
    elif profile == "dense":
        return np.arange(n_vocab, dtype=np.int64)
    else:
        pool = np.arange(n_vocab // 2, n_vocab)
    return np.unique(rng.choice(pool, size=6)).astype(np.int64)


# Edge profiles over a fixed 64-document corpus whose token df is known:
# df 0 for tokens 0, 7, 9; 32 for 1 and 2; 16 for 3 and 4; 64 for 5; 8
# for 6; 5 for 8. Each maps to (query tokens, floor of the padded table).
EDGE_PROFILES = {
    "pow2_fill": ([1, 2], 8),         # Σ df 64 == p_bucket, sentinels drop
    "pow2_fill_empty_last": ([1, 2, 7], 4),
    "empty_first": ([0, 3, 8], 8),
    "empty_middle": ([3, 7, 8], 8),
    "empty_last": ([1, 3, 8, 9], 1),  # U == 4: no sentinel after the run
    "u1": ([8], 1),
    "u1_fill": ([5], 1),
    "all_sentinel": ([], 8),
    "all_sentinel_u1": ([], 1),
}


def _edge_corpus() -> list:
    docs = []
    for j in range(64):
        toks = [5]
        toks += [1] if j < 32 else []
        toks += [2] if j % 2 == 0 else []
        toks += [3] if j < 16 else []
        toks += [4] if j % 4 == 0 else []
        toks += [6] if j < 8 else []
        toks += [8] if j < 5 else []
        docs.append(np.array(toks, np.int32))
    return docs


# -- tentpole: device fragment builder == host fragment_plan ------------------

@pytest.mark.parametrize("profile", ["head", "tail", "dense",
                                     *EDGE_PROFILES])
def test_device_plan_matches_host_byte_for_byte(profile, rng):
    if profile in EDGE_PROFILES:
        tokens, u_floor = EDGE_PROFILES[profile]
        idx = build_index(_edge_corpus(), 10, params=BM25Params())
        uniq = np.array(tokens, np.int64)
    else:
        u_floor = 8
        corpus = make_corpus(rng, n_docs=120, n_vocab=48, max_len=25)
        idx = build_index(corpus, 48, params=BM25Params())
        uniq = _profile_uniq(rng, profile, 48)
    di = DeviceIndex.build(idx, block_size=16, tile=16, frag=8,
                           with_blocked=False)
    fp = fragment_plan(idx, uniq, block_size=16, frag=8)
    sum_df = int(np.diff(idx.indptr)[uniq].sum())
    desc, dids, nf_used, _ = plan_fragments_device(
        di, _pad_uniq(uniq, floor=u_floor), sum_df=sum_df, k=5,
        block_size=16, nf_bucket=fp.nf_pad)
    assert nf_used == fp.nf_pad
    np.testing.assert_array_equal(np.asarray(desc), fp.desc)
    np.testing.assert_array_equal(
        np.asarray(dids),
        default_doc_ids(fp.vis_blocks, 5, int(idx.doc_lens.size), 16))


def _elements(tensor_type: str) -> int:
    """``tensor<16384x1xi32>`` -> 16384 (element count of a type)."""
    return int(np.prod([int(d) for d in tensor_type.split("x")[:-1]]))


def test_builder_has_no_stream_sized_search_or_run_gathers():
    """The owner/offset of each stream position comes from prefix sums:
    no ``while`` (a ``searchsorted`` scan) carries a stream-sized array,
    and the one stream-sized gather is the read of the postings."""
    import re
    import jax
    import jax.numpy as jnp
    p_bucket = 2 ** 14
    i32 = jnp.int32
    text = build_fragment_table.lower(
        jax.ShapeDtypeStruct((64,), i32), jax.ShapeDtypeStruct((1001,), i32),
        jax.ShapeDtypeStruct((1, 2 ** 15), i32), block_size=512, frag=128,
        nf_pad=2 ** 10, p_bucket=p_bucket, k=10, n_docs=5000).as_text()
    lines = text.splitlines()
    whiles = [ln for ln in lines if "stablehlo.while" in ln]
    assert whiles                        # the def_ids searchsorted stays
    for ln in whiles:
        assert all(_elements(t) != p_bucket
                   for t in re.findall(r"tensor<([^>]*)>", ln)), ln
    gathers = [ln for ln in lines if "stablehlo.gather" in ln]
    stream = [ln for ln in gathers
              if _elements(re.findall(r"-> tensor<([^>]*)>", ln)[-1])
              == p_bucket]
    assert len(stream) == 1, stream
    assert "tensor<1x32768xi32>" in stream[0]    # doc_ids_res[0, pos]


def test_device_plan_empty_query_and_df0_tokens(rng):
    corpus = make_corpus(rng, n_docs=40, n_vocab=64, max_len=10)
    idx = build_index(corpus, 64, params=BM25Params())
    di = DeviceIndex.build(idx, block_size=16, tile=16, frag=8,
                           with_blocked=False)
    df = np.diff(idx.indptr)
    cases = [np.zeros(0, np.int64)]
    if (df == 0).any():                           # df-0 tokens: no fragments
        cases.append(np.flatnonzero(df == 0)[:3].astype(np.int64))
    for uniq in cases:
        fp = fragment_plan(idx, uniq, block_size=16, frag=8)
        sum_df = int(df[uniq].sum())
        desc, dids, _, _ = plan_fragments_device(
            di, _pad_uniq(uniq), sum_df=sum_df, k=4, block_size=16,
            nf_bucket=fp.nf_pad)
        assert fp.n_frags == 0
        np.testing.assert_array_equal(np.asarray(desc), fp.desc)
        np.testing.assert_array_equal(
            np.asarray(dids),
            default_doc_ids(fp.vis_blocks, 4, int(idx.doc_lens.size), 16))


def test_device_plan_overflow_flag_and_retry(rng):
    """A too-small nf bucket must RAISE the flag, and the wrapper must
    retry to a bucket that reproduces the host table exactly — overflow is
    a retry signal, never silent truncation."""
    corpus = make_corpus(rng, n_docs=120, n_vocab=32, max_len=25)
    idx = build_index(corpus, 32, params=BM25Params())
    di = DeviceIndex.build(idx, block_size=16, tile=16, frag=8,
                           with_blocked=False)
    uniq = np.arange(32, dtype=np.int64)          # dense: many fragments
    sum_df = int(np.diff(idx.indptr).sum())
    fp = fragment_plan(idx, uniq, block_size=16, frag=8)
    assert fp.n_frags > 8                         # 8 really is too small
    import jax.numpy as jnp
    _, _, nf, over = build_fragment_table(
        jnp.asarray(_pad_uniq(uniq)), di.csc_indptr, di.csc_doc_ids,
        block_size=16, frag=8, nf_pad=8,
        p_bucket=bucket_pow2(sum_df, floor=8), k=5,
        n_docs=int(idx.doc_lens.size))
    assert bool(over) and int(nf) == fp.n_frags
    desc, _, nf_used, nf = plan_fragments_device(
        di, _pad_uniq(uniq), sum_df=sum_df, k=5, block_size=16,
        nf_bucket=8)                              # starts too small
    assert nf_used >= bucket_pow2(fp.n_frags, floor=8)
    assert nf == fp.n_frags
    ref = fragment_plan(idx, uniq, block_size=16, frag=8,
                        nf_bucket=nf_used)
    np.testing.assert_array_equal(np.asarray(desc), ref.desc)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2 ** 31), block_size=st.sampled_from([8, 16, 32]),
       frag=st.sampled_from([4, 8, 16]))
def test_property_device_plan_equals_host(seed, block_size, frag):
    rng = np.random.default_rng(seed)
    v = int(rng.integers(10, 60))
    corpus = [rng.integers(0, v, size=rng.integers(1, 20)).astype(np.int32)
              for _ in range(int(rng.integers(10, 150)))]
    idx = build_index(corpus, v, params=BM25Params())
    di = DeviceIndex.build(idx, block_size=block_size, tile=16, frag=frag,
                           with_blocked=False)
    uniq = np.unique(
        rng.integers(0, v, size=rng.integers(1, 12))).astype(np.int64)
    fp = fragment_plan(idx, uniq, block_size=block_size, frag=frag)
    sum_df = int(np.diff(idx.indptr)[uniq].sum())
    k = int(rng.integers(1, 8))
    desc, dids, _, _ = plan_fragments_device(
        di, _pad_uniq(uniq), sum_df=sum_df, k=k, block_size=block_size,
        nf_bucket=fp.nf_pad)
    np.testing.assert_array_equal(np.asarray(desc), fp.desc)
    np.testing.assert_array_equal(
        np.asarray(dids),
        default_doc_ids(fp.vis_blocks, k, int(idx.doc_lens.size),
                        block_size))


def test_device_plan_wrapper_estimates_without_nf_bucket(rng):
    """The estimate/state path (no explicit nf_bucket) must still cover
    the real fragment count and remember the bucket across batches."""
    corpus = make_corpus(rng, n_docs=100, n_vocab=32, max_len=25)
    idx = build_index(corpus, 32, params=BM25Params())
    di = DeviceIndex.build(idx, block_size=16, tile=16, frag=8,
                           with_blocked=False)
    uniq = np.arange(32, dtype=np.int64)
    sum_df = int(np.diff(idx.indptr).sum())
    state = {}
    desc, _, nf_used, _ = plan_fragments_device(
        di, _pad_uniq(uniq), sum_df=sum_df, k=5, block_size=16, state=state)
    fp = fragment_plan(idx, uniq, block_size=16, frag=8, nf_bucket=nf_used)
    np.testing.assert_array_equal(np.asarray(desc), fp.desc)
    assert state["nf"] == nf_used                 # steady-state memory


def test_device_plan_requires_resident_csc(rng):
    corpus = make_corpus(rng, n_docs=30, n_vocab=16)
    idx = build_index(corpus, 16, params=BM25Params())
    di = DeviceIndex.build(idx, with_csc=False)
    with pytest.raises(ValueError, match="resident CSC"):
        plan_fragments_device(di, _pad_uniq(np.array([1])), sum_df=3, k=2)


# -- tentpole: double-buffered DMA schedule == single-buffer oracle -----------

@pytest.mark.parametrize("method", ALL_VARIANTS)
def test_double_buffer_bit_identical_all_variants(method, rng):
    """Same scatter/fold math, different copy schedule — outputs must be
    BIT-identical, not just close (the acceptance criterion)."""
    corpus = make_corpus(rng, n_docs=90, n_vocab=64, max_len=20)
    idx = build_index(corpus, 64, params=BM25Params(method=method))
    kw = dict(regime="gathered", gather="resident", plan="device", **SMALL)
    db = DeviceRetriever(idx, **kw)
    sb = DeviceRetriever(idx, double_buffer=False, **kw)
    assert db.double_buffer and not sb.double_buffer
    queries = [rng.integers(0, 64, size=rng.integers(1, 6)).astype(np.int32)
               for _ in range(4)]
    for k in (1, 7):
        ids_db, vals_db = db.retrieve_batch(queries, k)
        ids_sb, vals_sb = sb.retrieve_batch(queries, k)
        np.testing.assert_array_equal(ids_db, ids_sb)
        np.testing.assert_array_equal(vals_db, vals_sb)   # bitwise
    # and both are exact against the oracle
    sc = ScipyBM25(idx)
    for i, q in enumerate(queries):
        oracle = sc.score(q)
        _, ref_v = topk_numpy(oracle[None], 7)
        np.testing.assert_allclose(vals_db[i], ref_v[0], atol=1e-4)
        np.testing.assert_allclose(oracle[ids_db[i]], vals_db[i], atol=1e-4)


def test_double_buffer_kernel_direct_single_fragment(rng):
    """Degenerate grids (1 fragment; all-padding table) through the raw
    kernel — the warm-up/prefetch/wait schedule must stay balanced."""
    import jax.numpy as jnp

    from repro.kernels.bm25_gather_score import bm25_resident_score_topk
    corpus = make_corpus(rng, n_docs=20, n_vocab=8, max_len=6)
    idx = build_index(corpus, 8, params=BM25Params())
    di = DeviceIndex.build(idx, block_size=32, tile=16, frag=8,
                           with_blocked=False)
    weights = jnp.zeros((8, 4), jnp.float32).at[0, :].set(1.0)
    for desc_np in (
        fragment_plan(idx, np.array([0]), block_size=32, frag=8).desc,
        np.zeros((6, 8), np.int32),               # nothing valid at all
    ):
        outs = [bm25_resident_score_topk(
            jnp.asarray(desc_np), weights, di.csc_doc_ids, di.csc_scores,
            block_size=32, frag=8, k=3, n_docs=int(idx.doc_lens.size),
            double_buffer=flag) for flag in (True, False)]
        np.testing.assert_array_equal(np.asarray(outs[0][0]),
                                      np.asarray(outs[1][0]))
        np.testing.assert_array_equal(np.asarray(outs[0][1]),
                                      np.asarray(outs[1][1]))


# -- acceptance: zero posting AND descriptor bytes with plan="device" ---------

def test_device_plan_ships_zero_posting_and_descriptor_bytes(rng):
    """THE acceptance invariant: with plan="device" the steady-state batch
    ships NOTHING through the counted posting/descriptor channels — the
    fragment table is born on device. plan="host" on the same index still
    ships descriptors (the contrast that proves the counter works)."""
    corpus = make_corpus(rng, n_docs=120, n_vocab=60)
    idx = build_index(corpus, 60, params=BM25Params(method="lucene"))
    dr = DeviceRetriever(idx, regime="auto", gather="resident",
                         plan="device", **SMALL)
    dr.warmup(k=5)
    qs = [rng.integers(0, 60, size=4).astype(np.int32) for _ in range(5)]
    dr.retrieve_batch(qs, 5)                      # settle the nf bucket
    reset_transfer_stats()
    for regime in (None, "blocked", "gathered"):
        for _ in range(2):
            dr.retrieve_batch(qs, 5, regime=regime)
    assert TRANSFERS.posting_uploads == 0, vars(TRANSFERS)
    assert TRANSFERS.posting_bytes == 0
    assert TRANSFERS.descriptor_uploads == 0, vars(TRANSFERS)
    assert TRANSFERS.descriptor_bytes == 0
    assert dr.last_plan.plan == "device"
    # contrast: host planning ships the descriptor table every batch
    hp = DeviceRetriever(idx, regime="gathered", gather="resident",
                         plan="host", **SMALL)
    hp.retrieve_batch(qs, 5)
    reset_transfer_stats()
    hp.retrieve_batch(qs, 5)
    assert TRANSFERS.posting_bytes == 0           # postings stay zero
    assert TRANSFERS.descriptor_bytes > 0         # but descriptors flowed
    assert hp.last_plan.plan == "host"


def test_host_arrays_drop_serves_exact_without_host_csc(rng):
    """host_arrays="drop" releases the O(nnz) host posting copy; serving
    must stay exact end-to-end from the resident arrays alone."""
    corpus = make_corpus(rng, n_docs=100, n_vocab=50)
    idx = build_index(corpus, 50, params=BM25Params(method="robertson"))
    dr = DeviceRetriever(idx, regime="gathered", gather="resident",
                         plan="device", host_arrays="drop", **SMALL)
    assert dr.dindex.host is None
    assert dr.index.doc_ids.size == 0 and dr.index.scores.size == 0
    assert idx.doc_ids.size > 0                   # caller's copy untouched
    sc = ScipyBM25(idx)
    queries = [rng.integers(0, 50, size=rng.integers(1, 5)).astype(np.int32)
               for _ in range(3)]
    ids, vals = dr.retrieve_batch(queries, 6)
    for i, q in enumerate(queries):
        oracle = sc.score(q)
        _, ref_v = topk_numpy(oracle[None], 6)
        np.testing.assert_allclose(vals[i], ref_v[0], atol=1e-4)
        np.testing.assert_allclose(oracle[ids[i]], vals[i], atol=1e-4)


def test_drop_mode_guards():
    rng = np.random.default_rng(0)
    corpus = make_corpus(rng, n_docs=20, n_vocab=10)
    idx = build_index(corpus, 10, params=BM25Params())
    with pytest.raises(ValueError, match="device"):
        DeviceRetriever(idx, regime="gathered", gather="resident",
                        plan="host", host_arrays="drop", **SMALL)
    with pytest.raises(ValueError, match="resident"):
        DeviceRetriever(idx, regime="gathered", gather="host",
                        plan="device", **SMALL)
    with pytest.raises(ValueError, match="host_arrays"):
        DeviceIndex.build(idx, host_arrays="free")


# -- core: planner discounts the free device descriptor build -----------------

def test_planner_device_plan_discount():
    """A work ratio between the discounted and full crossover gathers
    under device planning but full-scans under host planning; explicit
    crossovers are honored verbatim either way."""
    ratio = (DEFAULT_CROSSOVER * DEVICE_PLAN_DISCOUNT
             + DEFAULT_CROSSOVER) / 2.0
    nnz, sum_df = int(ratio * 1000), 1000
    host = plan_retrieval(sum_df, nnz, plan="host")
    dev = plan_retrieval(sum_df, nnz, plan="device")
    assert host.regime == "blocked" and host.plan == "host"
    assert dev.regime == "gathered" and dev.plan == "device"
    assert dev.crossover == pytest.approx(
        DEFAULT_CROSSOVER * DEVICE_PLAN_DISCOUNT)
    pinned = plan_retrieval(sum_df, nnz, plan="device", crossover=5.0)
    assert pinned.crossover == 5.0 and pinned.regime == "blocked"
    with pytest.raises(ValueError, match="plan mode"):
        plan_retrieval(1, 1, plan="tpu")


# -- serve: engine end-to-end with device planning ----------------------------

def test_engine_device_plan_exact_and_rescale(rng):
    corpus = make_corpus(rng, n_docs=90, n_vocab=40)
    p = BM25Params(method="bm25l")
    shards = build_sharded_indexes(corpus, 40, 3, params=p)
    eng = RetrievalEngine(shards, k=7, deadline_s=30.0, scorer="auto",
                          scorer_opts=dict(gather="resident",
                                           plan="device", **SMALL))
    qs = [rng.integers(0, 40, size=5).astype(np.int32) for _ in range(4)]
    rb = eng.retrieve_batch(qs)
    assert rb.ids.shape == (4, 7) and not rb.degraded
    for i, q in enumerate(qs):
        oracle = dense_oracle_scores(corpus, 40, q, p)
        _, ref_v = topk_numpy(oracle[None], 7)
        np.testing.assert_allclose(rb.scores[i], ref_v[0], atol=1e-3)
    eng.rescale(2)                                # boundaries move
    rb2 = eng.retrieve_batch(qs)
    for i, q in enumerate(qs):
        oracle = dense_oracle_scores(corpus, 40, q, p)
        _, ref_v = topk_numpy(oracle[None], 7)
        np.testing.assert_allclose(rb2.scores[i], ref_v[0], atol=1e-3)
