"""Batch records of the serving stack (``repro.obs``).

* spans nest on a thread (parent, self time) and the ring keeps the last
  ``RING`` batches;
* a frontend request's ``frontend.queue`` span and the pack and execute
  spans of its batch land in one record, across the frontend's threads;
* the device fragment builder counts its builds, stream positions,
  fragments and fragment slots;
* the stack's stage timers are the spans' durations, and rungs other
  than the resident gather run inside their ``hop.<rung>`` span;
* the spans reach the host plane of a profiler trace.
"""

import glob
import os
import time

import numpy as np
import pytest

import jax

from repro import obs
from repro.core import BM25Params, build_index
from repro.data.corpus import zipf_corpus, zipf_queries
from repro.serve import DeviceRetriever, RetrievalEngine, ServingFrontend
from repro.sparse.block_csr import DeviceIndex, bucket_pow2, fragment_plan
from repro.sparse.fragment_device import plan_fragments_device

# asserts the exact spans and counters of healthy batches, which an
# armed chaos fault legitimately changes
pytestmark = pytest.mark.no_chaos

N_VOCAB = 64
# the chip's serving defaults, spelled out for the CPU
CHIP_PATH = dict(gather="resident", plan="device", block_size=16, tile=16,
                 acc_block=16, frag=8, q_max=8)
RETRIEVER_SPANS = ("retriever.pack", "retriever.retrieve", "retriever.plan",
                   "fragments.build", "fragments.overflow_wait",
                   "kernel.dispatch", "board.wait", "board.finish")


@pytest.fixture(scope="module")
def index():
    return build_index(zipf_corpus(200, N_VOCAB, avg_len=20), N_VOCAB,
                       params=BM25Params())


@pytest.fixture(scope="module")
def retriever(index):
    return DeviceRetriever(index, **CHIP_PATH)


def _queries(n, seed=0):
    return zipf_queries(n, N_VOCAB, seed=seed)


def test_span_nesting_and_self_time():
    with obs.batch() as rec:
        with obs.span("outer") as outer:
            time.sleep(0.002)
            with obs.span("inner") as inner:
                time.sleep(0.003)
            with obs.span("inner"):
                time.sleep(0.001)
        obs.count("things", 7)
    assert obs.current() is None
    assert [s.name for s in rec.spans] == ["inner", "inner", "outer"]
    assert [s.parent for s in rec.spans] == ["outer", "outer", None]
    assert {s.batch for s in rec.spans} == {rec.id}
    assert rec.spans[-1].ns == outer.t1_ns - outer.t0_ns
    assert outer.seconds == pytest.approx(rec.spans[-1].ns / 1e9)
    assert inner.seconds >= 0.003
    inner_ns = rec.total_ns("inner")
    assert rec.self_ns("outer") == rec.total_ns("outer") - inner_ns
    assert rec.self_ns("outer") >= 2_000_000
    assert rec.self_ns("inner") == inner_ns
    assert rec.counters == {"things": 7}
    assert obs.batches()[-1] is rec


def test_outside_a_batch_nothing_is_recorded():
    n = len(obs.batches())
    last = obs.batches()[-1] if n else None
    with obs.span("loose") as sp:
        obs.count("loose", 1)
    assert sp.t1_ns >= sp.t0_ns
    after = obs.batches()
    assert len(after) == n and (not n or after[-1] is last)
    assert last is None or all(s.name != "loose" for s in last.spans)


def test_ring_keeps_the_last_batches():
    first = obs.new_batch()
    made = [obs.new_batch() for _ in range(obs.RING)]
    kept = obs.batches()
    assert len(kept) == obs.RING
    assert first not in kept
    assert kept == made                   # oldest first
    assert [r.id for r in kept] == sorted(r.id for r in kept)


def test_direct_batch_has_every_span_and_counter(retriever):
    res = retriever.retrieve_batch(_queries(6), 5)
    rec = obs.batches()[-1]
    names = {s.name for s in rec.spans}
    assert set(RETRIEVER_SPANS) <= names
    parent = {s.name: s.parent for s in rec.spans}
    assert parent["retriever.pack"] is None
    assert parent["retriever.retrieve"] is None
    for name in ("retriever.plan", "fragments.build",
                 "fragments.overflow_wait", "kernel.dispatch",
                 "board.wait", "board.finish"):
        assert parent[name] == "retriever.retrieve", name
    c = rec.counters
    assert c["sum_df"] == res.plan.sum_df
    assert c["frag_builds"] == 1
    assert c["stream_positions"] == bucket_pow2(res.plan.sum_df, floor=8)
    assert 0 < c["frags"] <= c["frag_slots"]
    # the planner's fragment count is the builder's, on the resident path
    assert res.plan.frags_planned == c["frags"]


@pytest.mark.parametrize("regime", ["blocked", "pruned"])
def test_other_rungs_run_inside_their_hop_span(retriever, regime):
    res = retriever.retrieve_batch(_queries(3, seed=5), 5, regime=regime)
    rec = obs.batches()[-1]
    (hop,) = rec.named(f"hop.{regime}")
    assert hop.parent == "retriever.retrieve"
    assert res.plan.regime == regime
    assert not rec.named("kernel.dispatch")


def test_stage_timers_are_the_spans(retriever):
    packed = retriever.pack_batch(_queries(4, seed=1))
    res = retriever.retrieve_batch(None, 5, packed=packed)
    rec = packed.record
    assert obs.batches()[-1] is rec
    (pack,) = rec.named("retriever.pack")
    (retrieve,) = rec.named("retriever.retrieve")
    assert packed.pack_s == pack.ns / 1e9
    assert res.timings["pack_s"] == pack.ns / 1e9
    assert res.timings["execute_s"] == retrieve.ns / 1e9
    assert res.latency_s == res.timings["total_s"] == pytest.approx(
        (pack.ns + retrieve.ns) / 1e9)
    assert pack.t1_ns <= retrieve.t0_ns


def test_frontend_request_and_batch_spans_share_a_record(retriever):
    qs = _queries(5, seed=2)
    with ServingFrontend(retriever, k=5, max_batch=len(qs),
                         batch_deadline_s=5.0) as fe:
        futs = [fe.submit(q) for q in qs]
        rows = [f.result(timeout=120) for f in futs]
    recs = [r for r in obs.batches()
            if any(s.name == "frontend.queue" for s in r.spans)]
    rec = recs[-1]
    queue = rec.named("frontend.queue")
    assert len(queue) == len(qs)
    assert sorted(row.timings["queue_s"] for row in rows) == sorted(
        s.ns / 1e9 for s in queue)
    names = [s.name for s in rec.spans]
    for name in ("frontend.pack_wait", "frontend.exec_wait",
                 *RETRIEVER_SPANS):
        assert name in names, name
    assert {s.batch for s in rec.spans} == {rec.id}
    (pack,) = rec.named("retriever.pack")
    (retrieve,) = rec.named("retriever.retrieve")
    (pack_wait,) = rec.named("frontend.pack_wait")
    (exec_wait,) = rec.named("frontend.exec_wait")
    # the hand-offs are cross-thread waits: recorded, without a parent
    assert pack_wait.parent is None and exec_wait.parent is None
    flush = queue[0].t1_ns
    assert {s.t1_ns for s in queue} == {flush}
    assert flush == pack_wait.t0_ns <= pack_wait.t1_ns <= pack.t0_ns
    assert pack.t1_ns <= exec_wait.t0_ns
    assert exec_wait.t1_ns <= retrieve.t0_ns
    for row in rows:
        assert row.timings["pack_s"] == pack.ns / 1e9
        assert row.timings["execute_s"] == retrieve.ns / 1e9


def test_engine_fanout_and_merge_spans(index):
    eng = RetrievalEngine([index], k=5, scorer="scipy", warmup=False)
    try:
        res = eng.retrieve_batch(_queries(3, seed=3))
    finally:
        eng._pool.shutdown(wait=True)
    rec = obs.batches()[-1]
    (fan,) = rec.named("engine.fanout")
    (merge,) = rec.named("engine.merge")
    assert fan.t1_ns <= merge.t0_ns
    assert res.latency_s >= (fan.ns + merge.ns) / 1e9


def _builder_inputs(index, nf_bucket_of):
    di = DeviceIndex.build(index, block_size=16, tile=16, frag=8,
                           with_blocked=False)
    uniq = np.arange(N_VOCAB, dtype=np.int64)
    sum_df = int(np.diff(index.indptr).sum())
    fp = fragment_plan(index, uniq, block_size=16, frag=8)
    tab = np.full(bucket_pow2(uniq.size, floor=8), np.iinfo(np.int32).max,
                  np.int32)
    tab[:uniq.size] = uniq
    return di, tab, sum_df, fp, nf_bucket_of(fp.n_frags)


def test_overflow_retry_counts_two_builds(index):
    di, tab, sum_df, fp, small = _builder_inputs(
        index, lambda n: bucket_pow2(n, floor=8) // 2)
    assert fp.n_frags > small           # the first build overflows
    with obs.batch() as rec:
        desc, _, nf_pad, nf = plan_fragments_device(
            di, tab, sum_df=sum_df, k=5, block_size=16, nf_bucket=small)
    assert nf_pad == 2 * small and nf == fp.n_frags
    assert len(rec.named("fragments.build")) == 2
    assert len(rec.named("fragments.overflow_wait")) == 2
    assert rec.counters["frag_builds"] == 2
    assert rec.counters["frags"] == fp.n_frags
    assert rec.counters["frag_slots"] == nf_pad


def test_stream_positions_are_the_builders_bucket(index):
    di, tab, sum_df, fp, bucket = _builder_inputs(
        index, lambda n: bucket_pow2(n, floor=8))
    with obs.batch() as rec:
        plan_fragments_device(di, tab, sum_df=sum_df, k=5, block_size=16,
                              nf_bucket=bucket)
    p_bucket = bucket_pow2(sum_df, floor=8)
    assert rec.counters["stream_positions"] == p_bucket
    assert rec.counters["frag_builds"] == 1
    assert rec.counters["frags"] == fp.n_frags
    # at the Σ df bucket the table always fits: no read-back, no count
    with obs.batch() as at_cap:
        *_, nf = plan_fragments_device(di, tab, sum_df=sum_df, k=5,
                                       block_size=16, nf_bucket=p_bucket)
    assert nf is None and "frags" not in at_cap.counters
    assert not at_cap.named("fragments.overflow_wait")
    assert at_cap.counters["stream_positions"] == p_bucket


def test_spans_reach_the_profilers_host_plane(retriever, tmp_path):
    from jax.profiler import ProfileData
    qs = _queries(6, seed=4)
    retriever.retrieve_batch(qs, 5)             # compiled before the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        retriever.retrieve_batch(qs, 5)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    host = {ev.name for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    for name in ("retriever.plan", "fragments.overflow_wait", "board.wait"):
        assert name in host, name
