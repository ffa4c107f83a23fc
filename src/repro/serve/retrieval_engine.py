"""Batched retrieval serving with shard hedging, deadlines and elasticity.

The paper's §2 "Multi-threading" uses pooled executors for retrieval
speedup; at pod scale the same executor pattern becomes the scatter-gather
layer over document shards, and the operational concerns become:

* stragglers — the global merge proceeds once a QUORUM of shard top-k lists
  has arrived by the deadline; late shards are dropped from that response
  (recorded as ``degraded``) instead of stalling the tail latency. Because
  per-shard top-k is a superset property, a missed shard can only remove
  candidates it owns — results from responsive shards stay exact.
* elasticity — ``rescale(n_shards)`` re-buckets the postings (pure host
  re-slicing, ``core.index.reshard_index``) when the pool grows/shrinks;
  shards whose postings are byte-identical after the reshard KEEP their
  runtime (device arrays stay resident, no re-upload, no re-warmup —
  ``engine.last_build_stats`` reports the reuse count).

* device offload — each ``ShardRuntime`` scores either host-side
  (``scorer="scipy"``, the paper's CSC slice+sum) or through ONE device
  scorer, :class:`DeviceRetriever` (``scorer="auto"``), built on an
  HBM-resident ``sparse.block_csr.DeviceIndex``: the shifted CSC posting
  arrays AND the block-bucketed full-scan layout are uploaded once at
  build/rescale and live on device across calls. Per batch the planner
  (``core.retrieval.plan_retrieval``) compares the batch's Σ df — free,
  from the host descriptor table — against nnz and picks the regime:

    - **full-scan**  (O(nnz), ``bm25_block_score_topk``) when the batch is
      dense enough that every posting tile would be gathered anyway;
    - **gathered**   (O(Σ df), ``bm25_resident_score_topk``) everywhere
      else — run-fragment descriptors go to SMEM, posting tiles are DMA'd
      straight out of the resident index, and the steady-state path ships
      ZERO posting bytes host→device (a host-gather fallback with a
      hot-token LRU remains for CPU/interpret mode);
    - **pruned**     (O(postings that can still win),
      ``bm25_resident_score_topk_pruned``) when the resident block-max
      table estimates enough provably-losing blocks — the gathered
      machinery minus every fragment whose document block cannot beat
      the certified top-k threshold. Output stays bit-identical.

  ``scorer="blocked"`` / ``scorer="gathered"`` / ``scorer="pruned"``
  remain as forced-regime aliases of the same class.

* batching — ``retrieve_batch`` runs B queries through ONE kernel launch
  per shard (the batch dimension is free on the MXU), amortizing launch
  and membership-table cost across the batch; per-query ``retrieve``
  stays for latency-sensitive single queries.

``ShardRuntime`` is process-local here (threads simulate shard servers; a
``delay`` hook lets tests inject stragglers), but the engine logic —
quorum, deadline, merge, re-shard — is exactly the production control
plane.
"""

from __future__ import annotations

import threading
import time
import warnings
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Sequence

import numpy as np

from .. import obs
from ..core.index import BM25Index, reshard_index
from ..core.reference import ScipyBM25
from ..core.retrieval import merge_topk
from .errors import (ExecutionStalledError, ResidencyError,
                     RetrievalConfigError, RetrievalError,
                     ScoreIntegrityError)
from .health import health_envelope, merge_fault_counts
from .overload import CircuitBreaker, RetryPolicy, WatchdogExecutor
from .results import PackedBatch, RetrievalResult


def _empty_batch(n_queries: int):
    ids = np.zeros((n_queries, 0), dtype=np.int64)
    scores = np.zeros((n_queries, 0), dtype=np.float32)
    return ids, scores


def _faults_module():
    """The fault harness, if (and only if) something already imported it."""
    import sys
    return sys.modules.get("repro.serve.faults")


class _DeviceRetrieverBase:
    """Shared host half of the device scorers (query packing + warmup).

    Subclasses set ``index``, ``n_docs``, ``q_max`` in ``__init__`` and
    implement ``retrieve_batch``; the packing helper and the single-query /
    warmup conveniences live here so the bucketing and no-truncation
    invariants have exactly ONE implementation.
    """

    def _pack_batch(self, query_tokens):
        """Batch -> padded query tables, every device dim pow2-bucketed.

        Three shape dimensions are bucketed so jit recompiles stay
        O(log demand) each, none silently truncating:

        * batch ``B`` — padded with empty queries (a ragged client batch
          must not trigger a fresh multi-second compile per distinct size);
        * per-query width — bucketed from the longest query (width ≥ query
          length ≥ its unique count, so ``pad_queries`` never truncates,
          unlike a fixed q_max that would quietly keep only the
          highest-count tokens of a long query);
        * unique-token table ``u_max`` — bucketed from the batch's actual
          distinct-token count.

        The token stream is sorted ONCE (``pad_queries``'s lexsort); the
        batch-unique table comes from its run set (``return_uniq``) and is
        reused for the pack table and the posting-run gather.

        Returns ``(b_true, uniq_batch, uniq_tab [u], weights [u, B],
        shift [B])`` — callers slice device outputs back to ``b_true``.
        """
        from ..core.scoring import bucket_pow2, pad_queries
        from ..sparse.block_csr import (pack_query_batch,
                                        query_nonoccurrence_shift)
        qs = [np.asarray(q).ravel() for q in query_tokens]
        b_true = len(qs)
        b_pad = bucket_pow2(max(b_true, 1), floor=8)
        qs += [np.zeros(0, np.int32)] * (b_pad - b_true)
        width = bucket_pow2(max((q.size for q in qs), default=1) or 1,
                            floor=self.q_max)
        toks, wts, uniq_batch = pad_queries(qs, width, return_uniq=True)
        u_max = bucket_pow2(max(uniq_batch.size, 1), floor=self.q_max)
        uniq_tab, weights = pack_query_batch(toks, wts, u_max=u_max,
                                             uniq=uniq_batch)
        shift = query_nonoccurrence_shift(self.index.nonoccurrence, toks,
                                          wts)
        return b_true, uniq_batch, uniq_tab, weights, shift

    def warmup(self, *, k: int) -> None:
        """Compile the floor-bucket retrieve path at engine build.

        The compiled-fn cache per (bucket..., k) is jax.jit's own
        static-arg/shape cache — the power-of-two bucketing in
        ``_pack_batch`` is what keys it to O(log demand) entries; this call
        pre-populates the floor buckets (B ≤ 8, width/u_max ≤ q_max floor)
        so typical first live queries never pay tracing+compilation; bigger
        batches pay one compile per pow2 bucket, then never again.
        """
        if self.n_docs == 0 or k <= 0:
            return
        q = np.zeros(1, dtype=np.int32)
        self.retrieve_batch([q], min(k, self.n_docs))

    def retrieve(self, query_tokens: np.ndarray, k: int
                 ) -> RetrievalResult:
        """One query -> :class:`RetrievalResult` with ``[k]`` boards.

        The single-query row of :meth:`retrieve_batch`; unpacks as the
        legacy ``(ids, scores)`` tuple.
        """
        r = self.retrieve_batch([np.asarray(query_tokens)], k)
        return RetrievalResult(
            ids=r.ids[0], scores=r.scores[0], plan=r.plan,
            degradations=r.degradations, timings=r.timings,
            degraded=r.degraded, latency_s=r.latency_s)


class DeviceRetriever(_DeviceRetrieverBase):
    """ONE device scorer, two regimes, zero per-batch posting copies.

    Builds an HBM-resident ``sparse.block_csr.DeviceIndex`` at construction
    (posting arrays uploaded ONCE — both the block-bucketed full-scan
    layout and the CSC arrays the resident gather kernel DMAs from) and
    plans every batch through ``core.retrieval.plan_retrieval``:

    * ``regime="auto"`` (default) — compare the batch's modeled costs:
      full-scan O(nnz), gathered O(crossover × Σ df), and — when the
      block-max table is resident — PRUNED, the gathered cost scaled by
      the estimated surviving-work fraction over ``PRUNE_DISCOUNT``. The
      decision and the pruning evidence (``survivor_frac``,
      ``frags_planned/pruned/skipped``) are recorded in ``self.last_plan``
      for observability.
    * ``regime="blocked"`` / ``"gathered"`` / ``"pruned"`` — force that
      regime (the planner still runs, so the evidence is logged); these
      back the :class:`BlockedRetriever` / :class:`GatheredRetriever` /
      :class:`PrunedRetriever` aliases.

    The pruned regime is the resident gather plus exact block-max
    pruning (see :meth:`_retrieve_pruned`): identical output bit-for-bit,
    strictly less work — fragments whose document block provably cannot
    place a document in any query's top-k are compacted out before launch
    and skipped in-kernel once the running threshold saturates further.

    The gathered regime has two executions:

    * ``gather="resident"`` — fragment descriptors go to SMEM and the
      resident kernel DMAs posting tiles straight out of the
      resident index (double-buffered: fragment f+1's copies overlap f's
      scatter; ``double_buffer=False`` keeps the sequential oracle).
      Where the fragment table is built is the ``plan`` axis:

      - ``plan="device"`` — the table is jit-built FROM the resident CSC
        arrays (``sparse.fragment_device``); the host never reads its CSC
        copy and per-batch host→device traffic is query tables only —
        zero posting AND zero descriptor bytes (tier-1 asserts both).
        ``host_arrays="drop"`` then releases the host posting copy
        entirely (O(V)/O(n_docs) metadata stays).
      - ``plan="host"`` — ``fragment_plan`` walks the host CSC copy and
        ships the O(Σ df/frag) descriptor table per batch (the PR-3
        behavior; still zero posting bytes).

      Default ``plan=None`` resolves to device on TPU, host elsewhere
      (interpret mode favors the cheaper host build); ``last_plan.plan``
      records the choice per batch.
    * ``gather="host"`` — the candidate-compacted host gather (fallback
      for CPU/interpret mode, where fragment-at-a-time DMA interpretation
      is slow); ships O(Σ df) postings per batch, with a hot-token LRU
      (:class:`~repro.sparse.block_csr.PostingRunCache`) so Zipf-head
      tokens are re-gathered once, not per batch.

    Default ``gather=None`` resolves to resident on TPU, host elsewhere.

    Budgets stay **adaptive**: fragment counts, posting tiles and chunk
    counts are sized from the batch's ACTUAL demand, pow2-bucketed
    (``bucket_pow2``) so recompiles stay O(log max-demand) and nothing is
    ever silently truncated (the device fragment builder turns its
    nf-bucket overflow flag into a larger-bucket retry). ``acc_block``
    (host-gather chunk height) stays SMALL — the one-hot scatter costs
    ``acc_block`` MACs/posting, so big candidate sets get MORE chunks,
    keeping work linear in Σ df.
    """

    def __init__(self, index: BM25Index, *, regime: str = "auto",
                 block_size: int = 512, tile: int = 512,
                 acc_block: int = 512, q_max: int = 32, frag: int = 512,
                 crossover: float | None = None, gather: str | None = None,
                 plan: str | None = None, double_buffer: bool = True,
                 host_arrays: str = "keep", run_cache: int = 256,
                 bmax_dtype: str = "auto", reorder: str = "none",
                 reuse_from=None,
                 device_index=None, on_fault: str = "degrade",
                 watchdog_s: float | None = None, retry_budget: int = 0,
                 retry_backoff_s: float = 0.005, retry_seed: int = 0,
                 breaker_threshold: int | None = 3,
                 breaker_window_s: float = 30.0,
                 breaker_cooldown_s: float = 5.0):
        from ..sparse.block_csr import DeviceIndex, PostingRunCache
        if regime not in ("auto", "blocked", "gathered", "pruned"):
            raise RetrievalConfigError(f"unknown regime {regime!r}")
        if on_fault not in ("degrade", "raise"):
            raise RetrievalConfigError(f"unknown on_fault mode {on_fault!r}")
        if watchdog_s is not None and watchdog_s <= 0:
            raise RetrievalConfigError("watchdog_s must be positive "
                                       "(or None to disable)")
        if retry_budget < 0:
            raise RetrievalConfigError("retry_budget must be >= 0")
        if breaker_threshold is not None and breaker_threshold < 1:
            raise RetrievalConfigError("breaker_threshold must be >= 1 "
                                       "(or None to disable breakers)")
        if device_index is not None:
            # ADOPT a pre-built DeviceIndex (snapshot cold-start:
            # ``DeviceIndex.load`` already uploaded the resident arrays —
            # no rebuild, no re-upload). Geometry comes from the adopted
            # index; regime / gather / plan resolve to the layouts the
            # snapshot actually holds.
            if index is None:
                index = device_index.host
            if index is None:
                raise RetrievalConfigError(
                    "device_index= adoption needs a host BM25Index (the "
                    "adopted DeviceIndex was built with host=None)")
            block_size = device_index.block_size
            frag = device_index.frag
            if regime == "auto" and device_index.blk_tok is None:
                regime = ("pruned" if device_index.bmax is not None
                          else "gathered")
            if regime == "auto" and device_index.csc_doc_ids is None:
                regime = "blocked"
            host_intact = (int(index.doc_ids.size) == int(index.indptr[-1]))
            if not host_intact:
                # the snapshot was loaded host_arrays="drop": every
                # host-side path (host gather / host planner / oracle) is
                # gone, so force the resident device plan
                gather, plan, host_arrays = "resident", "device", "keep"
        if gather is None:
            import jax
            # pruning is a resident-path concept (it gates fragment DMAs
            # against the resident block-max table), so a forced pruned
            # build resolves to the resident gather even off-TPU
            gather = ("resident" if regime == "pruned"
                      or jax.default_backend() == "tpu" else "host")
        if gather not in ("resident", "host"):
            raise RetrievalConfigError(f"unknown gather mode {gather!r}")
        if regime == "pruned" and gather != "resident":
            raise RetrievalConfigError(
                'regime="pruned" gates resident fragment DMAs against the '
                'block-max table — it requires gather="resident"')
        if plan is None:
            import jax
            plan = ("device" if gather == "resident"
                    and jax.default_backend() == "tpu" else "host")
        if plan not in ("host", "device"):
            raise RetrievalConfigError(f"unknown plan mode {plan!r}")
        if plan == "device" and gather != "resident":
            raise RetrievalConfigError(
                'plan="device" builds fragment tables from the resident '
                'CSC arrays — it requires gather="resident"')
        if host_arrays not in ("keep", "drop"):
            raise RetrievalConfigError(
                f"unknown host_arrays mode {host_arrays!r}")
        if host_arrays == "drop" and plan != "device":
            raise RetrievalConfigError(
                'host_arrays="drop" removes the arrays the host fragment '
                'planner reads — it requires plan="device"')
        self.index = index
        self.regime = regime
        self.gather_mode = gather
        self.plan_mode = plan
        self.double_buffer = double_buffer
        self.q_max = q_max                       # bucket floor, not a cap
        self.block_size = block_size
        self.tile = tile
        self.acc_block = acc_block               # host-gather chunk height
        self.crossover = crossover
        self.n_docs = int(index.doc_lens.size)
        self.run_cache = (PostingRunCache(run_cache)
                          if gather == "host" and run_cache > 0 else None)
        if device_index is not None:
            self.dindex = device_index
        else:
            with_csc = (regime in ("auto", "gathered", "pruned")
                        and gather == "resident")
            self.dindex = DeviceIndex.build(
                index, block_size=block_size, tile=tile, frag=frag,
                with_blocked=regime in ("auto", "blocked"),
                with_csc=with_csc,
                with_bmax=with_csc and regime in ("auto", "pruned"),
                bmax_dtype=bmax_dtype, reorder=reorder,
                host_arrays=host_arrays, reuse_from=reuse_from)
        if getattr(self.dindex, "perm", None) is not None \
                and self.dindex.host is not None:
            # doc-id reordering: serve in the PERMUTED id space end to
            # end — host fragment planning, the host-gather rung and the
            # oracle rung all read the permuted host copy, so EVERY
            # ladder hop yields permuted local ids and one host-side
            # gather at the merge maps winners back to client ids (the
            # survivor estimate in retrieve_batch thereby consumes the
            # permuted block-max table and matching fragment plans)
            self.index = self.dindex.host
        self._nf_state = {}                      # steady-state nf bucket
        self.on_fault = on_fault
        # overload protection (PR 10): watchdog-guarded execution, seeded
        # bounded retry on transient residency faults, and per-rung
        # circuit breakers giving the ladder memory across batches
        self.watchdog_s = watchdog_s
        self._watchdog = (WatchdogExecutor(watchdog_s,
                                           name="retriever-watchdog")
                          if watchdog_s is not None else None)
        self._retry = RetryPolicy(budget=retry_budget,
                                  base_s=retry_backoff_s, seed=retry_seed)
        self._breakers = ({hop: CircuitBreaker(
            threshold=breaker_threshold, window_s=breaker_window_s,
            cooldown_s=breaker_cooldown_s) for hop in self._LADDER}
            if breaker_threshold is not None else None)
        # observability: ladder + sanitizer counters feeding engine
        # health(). Mutations go through _health_lock — the frontend's
        # pack/execute stages run concurrently with direct callers, and
        # counts must sum exactly under that interleaving.
        self._health_lock = threading.RLock()
        self.fault_counters: dict[str, int] = {}
        self.query_counters: dict[str, int] = {}
        self.degradation_counts: dict[str, int] = {}
        self.batches_served = 0
        self.batches_degraded = 0
        self.retry_count = 0
        self.last_queries: list[np.ndarray] = []
        self._oracle = None                      # lazy ScipyBM25 (last rung)
        if (host_arrays == "drop"
                and getattr(self.dindex, "perm", None) is None):
            # serving now reads only metadata: release the O(nnz) host
            # posting copy (a private stripped view — the caller's index
            # object is untouched). Under reordering ``self.index`` is
            # already the builder's stripped PERMUTED metadata copy —
            # re-stripping from the client-order ctor index would hand
            # the merge the wrong doc_lens order.
            from dataclasses import replace
            self.index = replace(index, doc_ids=np.zeros(0, np.int32),
                                 scores=np.zeros(0, np.float32))
        self.last_plan = None

    def warmup(self, *, k: int) -> None:
        """Compile BOTH resident regimes' floor buckets at engine build."""
        if self.n_docs == 0 or k <= 0:
            return
        q = np.zeros(1, dtype=np.int32)
        kk = min(k, self.n_docs)
        if (self.regime in ("auto", "blocked")
                and self.dindex.blk_tok is not None):
            self.retrieve_batch([q], kk, regime="blocked")
        if (self.regime in ("auto", "gathered")
                and (self.gather_mode == "host"
                     or self.dindex.csc_doc_ids is not None)):
            self.retrieve_batch([q], kk, regime="gathered")
        if self.regime == "pruned":
            # auto engines compile the pruned kernels lazily on the first
            # batch the cost model routes there — warming all three per
            # shard would triple build latency for a regime many shards
            # never enter
            self.retrieve_batch([q], kk, regime="pruned")

    def health(self) -> dict:
        """Schema-2 health report (see ``repro.serve`` package docstring).

        ``served``/``degraded`` count BATCHES at this level; ``degraded``
        means the exact-fallback ladder hopped at least once. Legacy
        spellings (``batches_served``/``batches_degraded``) ride along as
        level extras, as do the overload-protection counters:
        ``breakers`` (per-rung state machine snapshots), ``retries``
        (seeded-backoff re-attempts that saved a ladder hop) and
        ``watchdog`` (armed deadline + stall count).
        """
        now = time.monotonic()
        with self._health_lock:
            breakers = ({hop: br.snapshot(now)
                         for hop, br in self._breakers.items()}
                        if self._breakers is not None else {})
            return health_envelope(
                served=self.batches_served,
                degraded=self.batches_degraded,
                faults=dict(self.fault_counters),
                queries=dict(self.query_counters),
                batches_served=self.batches_served,
                batches_degraded=self.batches_degraded,
                degradations=dict(self.degradation_counts),
                breakers=breakers,
                retries=self.retry_count,
                watchdog=({"timeout_s": self._watchdog.timeout_s,
                           "stalls": self._watchdog.stalls}
                          if self._watchdog is not None else {}),
                snapshot=dict(getattr(self.dindex, "snapshot_report",
                                      None) or {}),
            )

    def save(self, path, *, algo: str | None = None) -> dict:
        """Persist this retriever's resident index (see sparse.snapshot)."""
        return self.dindex.save(path, index=self.index, algo=algo)

    # -- the graceful-degradation ladder ---------------------------------
    #
    # Five rungs, all EXACT: pruned -> gathered-resident -> host-gather ->
    # blocked full-scan -> numpy ScipyBM25 oracle. A typed RetrievalError
    # in one rung triggers the hop to the next AVAILABLE rung (capability
    # depends on the layouts this retriever was built with); results never
    # change across hops — only the cost — so degradation preserves the
    # paper's exactness guarantee by construction. The trail is recorded
    # in ``last_plan.degradations`` and aggregated into the counters the
    # engine-level ``health()`` report exposes.

    _LADDER = ("pruned", "resident", "host", "blocked", "oracle")

    def _host_postings_intact(self) -> bool:
        """False once ``host_arrays="drop"`` released the host copy."""
        return int(self.index.doc_ids.size) == int(self.index.indptr[-1])

    def _hop_available(self, hop: str, kk: int) -> bool:
        """Can this rung run with the layouts this retriever holds?"""
        if hop == "pruned":
            return (self.gather_mode == "resident"
                    and self.dindex.bmax is not None
                    and self.dindex.csc_doc_ids is not None
                    and kk <= self.dindex.block_size)
        if hop == "resident":
            return self.dindex.csc_doc_ids is not None and (
                self.plan_mode == "device" or self._host_postings_intact())
        if hop in ("host", "oracle"):
            return self._host_postings_intact()
        if hop == "blocked":
            return self.dindex.blk_tok is not None
        return False

    # -- per-rung circuit breakers (overload protection, PR 10) -----------

    def _breaker_allow(self, hop: str) -> bool:
        """May the ladder run this rung now? (half-open claims its probe)."""
        if self._breakers is None:
            return True
        with self._health_lock:
            return self._breakers[hop].allow(time.monotonic())

    def _breaker_record(self, hop: str, *, ok: bool) -> None:
        if self._breakers is None:
            return
        with self._health_lock:
            br = self._breakers[hop]
            if ok:
                br.record_success(time.monotonic())
            else:
                br.record_fault(time.monotonic())

    def trip_breaker(self, hop: str, *,
                     cooldown_s: float | None = None) -> None:
        """Operator override: force a rung's breaker open for a cooldown.

        The ladder then skips ``hop`` (recording a ``BreakerOpen`` trail
        entry) and serves exactly from the remaining rungs until the
        cooldown's half-open probe closes the breaker again. Raises
        :class:`RetrievalConfigError` when breakers are disabled
        (``breaker_threshold=None``) or ``hop`` is not a ladder rung.
        """
        if self._breakers is None:
            raise RetrievalConfigError(
                "circuit breakers are disabled on this retriever "
                "(breaker_threshold=None)")
        if hop not in self._breakers:
            raise RetrievalConfigError(
                f"unknown ladder rung {hop!r}; available: "
                f"{list(self._LADDER)}")
        with self._health_lock:
            self._breakers[hop].force_open(time.monotonic(),
                                           cooldown_s=cooldown_s)

    def _run_hop(self, hop, qs, b, uniq_batch, uniq_tab, weights, shift,
                 kk, plan, prune_ub, *, strict, guard_cm):
        """One execution attempt of a rung: the ``kernel.stall`` fault
        site, then ``_exec_hop`` — under the watchdog deadline when armed.

        The watchdog runs the body on its supervised worker thread, so
        the ladder guard scope (thread-local) is re-entered ON that
        thread via ``ctx=``; a deadline miss abandons the stalled worker
        and surfaces as :class:`ExecutionStalledError` tagged with the
        rung. Strict calls bypass the watchdog: warmup's forced-regime
        calls pay one-off compiles that a serving-sized deadline would
        misread as stalls.
        """
        record = obs.current()

        def body():
            _f = _faults_module()
            if _f is not None and _f.ACTIVE:
                _f.fire("kernel.stall")
            with obs.batch(record):      # the watchdog's worker records too
                return self._exec_hop(hop, qs, b, uniq_batch, uniq_tab,
                                      weights, shift, kk, plan, prune_ub)

        if self._watchdog is not None and not strict:
            try:
                return self._watchdog.run(body, ctx=guard_cm)
            except ExecutionStalledError as e:
                e.hop = hop
                raise
        with guard_cm():
            return body()

    def pack_batch(self, query_tokens: Sequence[np.ndarray], *,
                   strict: bool | None = None) -> PackedBatch:
        """Host half of :meth:`retrieve_batch`: fault hook + sanitizer +
        pow2 pack, split out so a front-end can OVERLAP packing batch
        i+1 with device execution of batch i.

        Runs exactly the stages ``retrieve_batch`` runs before planning —
        the ``query.batch`` fault site, the shared sanitizer
        (``core.retrieval.validate_query_batch``, counting repairs into
        ``query_counters``), and ``_pack_batch``'s pow2 bucketing — so
        ``retrieve_batch(None, k, packed=pack_batch(qs))`` is
        bit-identical to ``retrieve_batch(qs, k)`` by construction.
        ``strict`` mirrors the retrieve-side strictness (default: the
        constructor's ``on_fault``); strict packs surface faults instead
        of entering the recoverable guard scope.
        """
        with obs.batch() as record, obs.span("retriever.pack") as sp:
            packed = self._pack(query_tokens, strict)
        packed.record = record
        packed.pack_s = sp.seconds
        return packed

    def _pack(self, query_tokens, strict) -> PackedBatch:
        import contextlib

        from ..core.retrieval import validate_query_batch

        if strict is None:
            strict = self.on_fault == "raise"
        _f = _faults_module()
        # guarded faults target RECOVERABLE scopes only: a strict call
        # re-raises instead of degrading, so it never enters the guard —
        # chaos mode (guarded specs armed globally) cannot crash warmup's
        # forced-regime calls or an ``on_fault="raise"`` deployment. Test
        # strict surfacing with ``guarded=False`` specs.
        guard = (_f.guard if _f is not None and not strict
                 else contextlib.nullcontext)
        if _f is not None and _f.ACTIVE:
            with guard():
                query_tokens = _f.fire("query.batch", list(query_tokens),
                                       n_vocab=self.index.n_vocab)
        # sanitize into a LOCAL counter dict, merged under the health
        # lock: the frontend pack stage runs concurrently with direct
        # callers, and in-place mutation of the shared dict would drop
        # increments under that interleaving
        local_counts: dict[str, int] = {}
        qs = validate_query_batch(
            query_tokens, self.index.n_vocab,
            counters=local_counts,
            on_invalid="raise" if self.on_fault == "raise" else "sanitize")
        if local_counts:
            with self._health_lock:
                for key, v in local_counts.items():
                    self.query_counters[key] = \
                        self.query_counters.get(key, 0) + v
        if self.n_docs == 0:                     # empty shard post-rescale
            return PackedBatch(qs, len(qs), np.zeros(0, np.int32), None,
                               None, None)
        b, uniq_batch, uniq_tab, weights, shift = self._pack_batch(qs)
        return PackedBatch(qs, b, uniq_batch, uniq_tab, weights, shift)

    def retrieve_batch(self, query_tokens: Sequence[np.ndarray] | None,
                       k: int, *, regime: str | None = None,
                       packed: PackedBatch | None = None
                       ) -> RetrievalResult:
        """B queries -> :class:`RetrievalResult` with ``[B, k]`` boards,
        one launch per batch (unpacks as the legacy ``(ids, scores)``).

        ``regime`` overrides this call's plan (used by warmup and the
        benchmark sweep) and makes the call STRICT — a typed failure
        surfaces instead of degrading (a forced regime that cannot run is
        an operator error, not traffic to absorb). Normal traffic leaves
        it None: the cost model picks the entry rung and any typed
        failure walks the exact fallback ladder (see class docstring and
        ROADMAP "Fault tolerance"), recording each hop in the result's
        ``degradations`` (also ``last_plan.degradations``).
        ``on_fault="raise"`` (constructor) makes every call strict.
        Every returned board passes a cheap ``[B, k]`` finite-check; a
        NaN/Inf tile is a
        :class:`~repro.serve.errors.ScoreIntegrityError` — degraded
        around like any other fault.

        ``packed`` resumes from a prior :meth:`pack_batch` (the
        front-end's overlap path; ``query_tokens`` is then ignored and
        may be None) — the sanitizer and fault hook already ran at pack
        time, so results are bit-identical to the one-call path.
        """
        strict = regime is not None or self.on_fault == "raise"
        if packed is None:
            packed = self.pack_batch(query_tokens, strict=strict)
        with obs.batch(packed.record), \
                obs.span("retriever.retrieve") as sp:
            res = self._retrieve_packed(packed, k, regime, strict)
        res.timings = {"pack_s": packed.pack_s, "execute_s": sp.seconds,
                       "total_s": packed.pack_s + sp.seconds}
        res.latency_s = packed.pack_s + sp.seconds
        return res

    def _retrieve_packed(self, packed, k, regime, strict
                         ) -> RetrievalResult:
        """Plan, then walk the ladder from the entry rung (the execution
        half of :meth:`retrieve_batch`; timings are the caller's)."""
        import contextlib

        from ..core.retrieval import plan_retrieval

        _f = _faults_module()
        # recoverable-scope guard for the EXECUTION stages (see
        # pack_batch for the strictness rationale)
        guard = (_f.guard if _f is not None and not strict
                 else contextlib.nullcontext)
        qs = packed.qs
        self.last_queries = qs
        if self.n_docs == 0 or k <= 0:           # empty shard post-rescale
            ids0, sc0 = _empty_batch(len(qs))
            return RetrievalResult(ids=ids0, scores=sc0)
        b, uniq_batch, uniq_tab, weights, shift = (
            packed.b, packed.uniq_batch, packed.uniq_tab, packed.weights,
            packed.shift)
        kk = min(k, self.n_docs)
        # the pruned regime needs the block-max table and an accumulator
        # window matching its block grid (k can outgrow the block height)
        prune_ok = self._hop_available("pruned", kk)
        want = regime or self.regime
        survivor_frac, prune_ub = None, None
        with obs.span("retriever.plan"):
            # the host estimate feeds the auto cost model and (under host
            # planning) hands its bound matrix to the execution pass; a
            # FORCED pruned regime under device planning consumes neither
            # — skip the O(U·nb·B) host matmul on that hot path
            if prune_ok and (want == "auto" or (want == "pruned"
                                                and self.plan_mode == "host")):
                from ..sparse.block_csr import estimate_prune_survivors
                survivor_frac, prune_ub = estimate_prune_survivors(
                    self.dindex.bmax, uniq_tab, weights, k=kk, b_true=b)
            plan = plan_retrieval(self.dindex.sum_df(uniq_batch),
                                  self.dindex.nnz, regime=want,
                                  crossover=self.crossover,
                                  plan=self.plan_mode,
                                  survivor_frac=survivor_frac)
            obs.count("sum_df", plan.sum_df)
        self.last_plan = plan
        if plan.regime == "pruned" and not prune_ok:
            if self.gather_mode != "resident":
                raise RetrievalConfigError('regime="pruned" requires '
                                           'gather="resident"')
            if self.dindex.csc_doc_ids is None or self.dindex.bmax is None:
                raise ResidencyError("pruned regime requested but this "
                                     "retriever was built without the "
                                     "resident CSC index + block-max "
                                     "table")
            # k outgrew the block-max grid (degenerate: the scoreboard
            # spans whole blocks, nothing can prune) — run the exact
            # unpruned resident path under the pruned label
            plan = plan_retrieval(plan.sum_df, plan.nnz, regime="gathered",
                                  crossover=self.crossover,
                                  plan=self.plan_mode)
            plan.regime, plan.forced = "pruned", True
            self.last_plan = plan
            entry = "resident"
        elif plan.regime == "pruned":
            entry = "pruned"
        elif plan.regime == "blocked":
            entry = "blocked"
        else:
            entry = "resident" if self.gather_mode == "resident" else "host"

        trail = plan.degradations
        hops = ((entry,) if strict
                else self._LADDER[self._LADDER.index(entry):])
        last_err = None
        with self._health_lock:
            self.batches_served += 1
        for hop in hops:
            if hop != entry and not self._hop_available(hop, kk):
                continue
            if not strict and not self._breaker_allow(hop):
                # the breaker remembers this rung's recent faults: skip
                # it WITHOUT execution (no fault-then-hop tax) and let
                # the next rung fill the trail entry's "to"
                trail.append({"from": hop, "to": None,
                              "error": "BreakerOpen",
                              "detail": f"circuit breaker open for rung "
                                        f"{hop!r} (skipped without "
                                        f"execution)"})
                continue
            if trail and trail[-1]["to"] is None:
                trail[-1]["to"] = hop
            # transient-fault retry: seeded exponential backoff with a
            # bounded budget before burning a ladder hop (strict calls
            # surface the first fault instead)
            delays = self._retry.delays() if not strict else []
            while True:
                try:
                    ids, vals = self._run_hop(
                        hop, qs, b, uniq_batch, uniq_tab, weights, shift,
                        kk, plan, prune_ub, strict=strict, guard_cm=guard)
                    with obs.span("board.wait"):
                        vals = np.asarray(vals)
                    with obs.span("board.finish"):
                        return self._finish(hop, ids, vals, b, kk, plan)
                except RetrievalError as e:
                    name = type(e).__name__
                    with self._health_lock:
                        self.fault_counters[name] = \
                            self.fault_counters.get(name, 0) + 1
                    if strict:
                        raise
                    if isinstance(e, ResidencyError) and delays:
                        with self._health_lock:
                            self.retry_count += 1
                        time.sleep(delays.pop(0))
                        continue
                    self._breaker_record(hop, ok=False)
                    trail.append({"from": hop, "to": None, "error": name,
                                  "detail": str(e)})
                    last_err = e
                    break
        raise RetrievalError(
            f"every ladder hop failed or is unavailable (entry "
            f"{entry!r}, degradations {trail!r})") from last_err

    def _finish(self, hop, ids, vals, b, kk, plan) -> RetrievalResult:
        """Check the board and assemble the batch's result: a cheap
        integrity gate on the ``[B, k]`` board (NOT the full score
        matrix, which never materializes on these paths), the ladder's
        bookkeeping, and the id remap."""
        board = vals[:b].astype(np.float32, copy=False)
        if not np.isfinite(board).all():
            raise ScoreIntegrityError(
                f"non-finite entries in the [{b}, {kk}] score board "
                f"returned by the {hop!r} hop")
        self._breaker_record(hop, ok=True)
        trail = plan.degradations
        if trail:
            with self._health_lock:
                self.batches_degraded += 1
                for t in trail:
                    key = f"{t['from']}->{t['to']}"
                    self.degradation_counts[key] = \
                        self.degradation_counts.get(key, 0) + 1
        ids = np.asarray(ids)[:b].astype(np.int64)
        perm = getattr(self.dindex, "perm", None)
        if perm is not None:
            # doc-id reordering: every hop scored in the permuted id
            # space — ONE host-side gather on the [B, k] board maps
            # winners back to client ids (zero extra device bytes)
            from ..sparse.reorder import remap_board
            ids = remap_board(ids, board, perm)
        return RetrievalResult(
            ids=ids + self.index.doc_offset, scores=board, plan=plan,
            degradations=list(trail), degraded=bool(trail))

    def _exec_hop(self, hop, qs, b, uniq_batch, uniq_tab, weights, shift,
                  kk, plan, prune_ub):
        if hop == "resident":          # the served path: spans of its own
            return self._exec_resident(uniq_batch, uniq_tab, weights,
                                       shift, kk, plan)
        with obs.span(f"hop.{hop}"):
            if hop == "pruned":
                return self._retrieve_pruned(uniq_batch, uniq_tab, weights,
                                             shift, kk, plan, b_true=b,
                                             ub=prune_ub)
            if hop == "host":
                return self._exec_host(uniq_batch, uniq_tab, weights, shift,
                                       kk)
            if hop == "blocked":
                return self._exec_blocked(uniq_tab, weights, shift, kk)
            if hop == "oracle":
                return self._exec_oracle(qs, kk)
        raise AssertionError(f"unknown ladder hop {hop!r}")

    def _exec_blocked(self, uniq_tab, weights, shift, kk):
        import jax.numpy as jnp

        from ..kernels import ops
        if self.dindex.blk_tok is None:
            raise ResidencyError("blocked regime requested but this "
                                 "retriever was built gathered-only")
        return ops.bm25_retrieve_blocked(
            self.dindex.blk_tok, self.dindex.blk_loc, self.dindex.blk_sc,
            jnp.asarray(uniq_tab), jnp.asarray(weights),
            jnp.asarray(shift), block_size=self.dindex.block_size,
            n_docs=self.n_docs, k=kk, tile_p=self.dindex.tile_p)

    def _exec_resident(self, uniq_batch, uniq_tab, weights, shift, kk,
                       plan):
        import jax.numpy as jnp

        from ..core.retrieval import default_doc_ids
        from ..core.scoring import bucket_pow2
        from ..kernels import ops
        from ..sparse.block_csr import fragment_plan, put_descriptor_array
        if self.dindex.csc_doc_ids is None:
            raise ResidencyError("resident gather requested but this "
                                 "retriever was built blocked-only")
        # accumulator window grows only if k outruns it (the shard
        # scoreboard needs k ≤ block height); fragment count buckets
        # inside the planners
        rblock = bucket_pow2(kk, floor=self.block_size)
        if self.plan_mode == "device":
            # fragment table + default ids born ON device from the
            # resident CSC arrays — no host CSC read, no descriptor
            # upload (the tier-1 zero-descriptor-bytes invariant)
            from ..sparse.fragment_device import plan_fragments_device
            desc, dids, _, nf = plan_fragments_device(
                self.dindex, uniq_tab, sum_df=plan.sum_df, k=kk,
                block_size=rblock, state=self._nf_state)
            if nf is not None:
                plan.frags_planned = nf
        else:
            if not self._host_postings_intact():
                raise ResidencyError('plan="host" fragment planning needs '
                                     'the host posting arrays')
            fp = fragment_plan(self.index, uniq_batch, block_size=rblock,
                               frag=self.dindex.frag)
            dids = jnp.asarray(default_doc_ids(fp.vis_blocks, kk,
                                               self.n_docs, rblock))
            desc = put_descriptor_array(fp.desc)
            plan.frags_planned = fp.n_frags
        with obs.span("kernel.dispatch"):
            return ops.bm25_retrieve_resident(
                desc, jnp.asarray(weights),
                self.dindex.csc_doc_ids, self.dindex.csc_scores,
                dids, jnp.asarray(shift), block_size=rblock,
                frag=self.dindex.frag, k=kk, n_docs=self.n_docs,
                double_buffer=self.double_buffer)

    def _exec_host(self, uniq_batch, uniq_tab, weights, shift, kk):
        import jax.numpy as jnp

        from ..core.scoring import bucket_pow2
        from ..kernels import ops
        from ..sparse.block_csr import (gather_posting_runs,
                                        put_posting_arrays)
        if not self._host_postings_intact():
            raise ResidencyError("host gather needs the host posting "
                                 'arrays, which host_arrays="drop" '
                                 "released")
        # host-gather: chunk height grows only if k outruns it; posting/
        # chunk dims bucket inside the gather. The uploads below are the
        # per-batch posting copies the resident path eliminates — routed
        # through the counting helper on purpose.
        acc_block = bucket_pow2(kk, floor=self.acc_block)
        gp = gather_posting_runs(self.index, uniq_batch,
                                 acc_block=acc_block, tile=self.tile,
                                 cache=self.run_cache)
        tok, slot, sc, cand = put_posting_arrays(
            gp.token_ids, gp.slot_ids, gp.scores, gp.candidates)
        return ops.bm25_retrieve_gathered(
            tok, slot, sc, jnp.asarray(uniq_tab), jnp.asarray(weights),
            cand, jnp.asarray(shift), acc_block=gp.acc_block, k=kk,
            n_docs=self.n_docs, tile_p=min(self.tile, gp.p_pad))

    def _exec_oracle(self, qs, kk):
        """Terminal rung: the paper-faithful numpy/scipy scorer.

        Host-side and slow, but it cannot fail for device reasons — the
        ladder's floor. Exact by definition: it IS the reference the
        device regimes are tested against. Ids come back shard-local
        (the caller adds ``doc_offset``, same as every other hop).
        """
        if not self._host_postings_intact():
            raise ResidencyError('oracle fallback needs the host posting '
                                 'arrays, which host_arrays="drop" '
                                 "released")
        from ..core.retrieval import topk_numpy
        if self._oracle is None:
            self._oracle = ScipyBM25(self.index)
        b = len(qs)
        ids = np.zeros((b, kk), np.int64)
        vals = np.zeros((b, kk), np.float32)
        for i, q in enumerate(qs):
            s = self._oracle.score(q)
            idx, v = topk_numpy(s[None], kk)
            ids[i], vals[i] = idx[0], v[0]
        return ids, vals

    def _retrieve_pruned(self, uniq_batch, uniq_tab, weights, shift, kk,
                         plan, *, b_true, ub=None):
        """Block-max pruned resident execution (exact; see ROADMAP).

        Three stages, under either planner:

        1. **Seed** — the full fragment table is compacted down to the few
           highest-upper-bound blocks and scored through the single-buffer
           resident kernel; the resulting scoreboard's k-th row is a REAL
           document's full score per query, i.e. a certified lower bound
           on each final k-th score (the threshold τ).
        2. **Compact** — fragments of blocks whose summed query-side upper
           bound beats τ for NO query are compacted out of the table
           before launch (the seed blocks always survive: each holds a
           document scoring ≥ its own bound's τ contribution), and the
           fragment bucket re-sizes so the kernel grid shrinks with the
           surviving work.
        3. **Skip** — the survivors run through the pruned kernel, whose
           per-fragment scoreboard test keeps cutting DMAs as the running
           threshold saturates past the seed estimate mid-launch.

        Under ``plan="host"`` the bound matmul/compaction run on numpy
        and the compacted table + bound rows ship as descriptors; under
        ``plan="device"`` everything is derived from the resident
        block-max table and CSC arrays — zero descriptor bytes, same as
        the unpruned device plan. Default-document ids always come from
        the UNPRUNED visited-block set: a pruned block's documents score
        below τ, not zero.
        """
        import jax.numpy as jnp

        from ..core.retrieval import default_doc_ids
        from ..core.scoring import bucket_pow2
        from ..kernels import ops
        from ..kernels.bm25_gather_score import bm25_resident_score_topk
        from ..sparse.block_csr import (block_upper_bounds, fragment_plan,
                                        prune_fragment_plan,
                                        put_descriptor_array,
                                        select_seed_blocks)
        bm = self.dindex.bmax
        rblock = self.dindex.block_size
        frag = self.dindex.frag
        w_dev = jnp.asarray(weights)
        csc_doc, csc_sc = self.dindex.csc_doc_ids, self.dindex.csc_scores
        if self.plan_mode == "device":
            from ..sparse.fragment_device import (block_bounds_device,
                                                  compact_fragment_table,
                                                  plan_fragments_device,
                                                  prune_fragment_mask,
                                                  seed_fragment_mask)
            desc_full, dids, _, _ = plan_fragments_device(
                self.dindex, uniq_tab, sum_df=plan.sum_df, k=kk,
                block_size=rblock, state=self._nf_state)
            nf_planned = int(np.asarray((desc_full[1] > 0).sum()))
            ub_dev = block_bounds_device(
                bm.device, bm.scale_dev,
                jnp.asarray(np.asarray(uniq_tab, np.int32)), w_dev,
                quantized=bm.quantized)
            # pow2 batch-padding columns are sliced off after retrieval —
            # their trivial thresholds must not veto pruning (real empty
            # queries keep theirs: their all-tied folds must replay
            # exactly)
            col = jnp.arange(ub_dev.shape[1], dtype=jnp.int32)
            ub_dev = jnp.where(col[None, :] < b_true, ub_dev, -jnp.inf)
            from ..sparse.block_csr import seed_block_budget
            seed_keep = seed_fragment_mask(desc_full, ub_dev,
                                           n_seed=seed_block_budget(kk))
            seed_desc, n_sk = compact_fragment_table(desc_full, seed_keep)
            sb = bucket_pow2(max(int(n_sk), 1), floor=8)
            sv, _ = bm25_resident_score_topk(
                seed_desc[:, :sb], w_dev, csc_doc, csc_sc,
                block_size=rblock, frag=frag, k=kk, n_docs=self.n_docs,
                double_buffer=False)
            tau = sv[kk - 1]
            keep = prune_fragment_mask(desc_full, ub_dev, tau)
            desc_c, n_kp = compact_fragment_table(desc_full, keep)
            nf_surv = int(n_kp)
            desc = desc_c[:, :bucket_pow2(max(nf_surv, 1), floor=8)]
            bounds = ub_dev[desc[3], :]
        else:
            fp = fragment_plan(self.index, uniq_batch, block_size=rblock,
                               frag=frag)
            nf_planned = fp.n_frags
            if ub is None:
                ub = block_upper_bounds(bm, uniq_tab, weights)
                ub[:, b_true:] = -np.inf      # see device branch comment
            dids = jnp.asarray(default_doc_ids(fp.vis_blocks, kk,
                                               self.n_docs, rblock))
            if fp.n_frags:
                seed_keep = select_seed_blocks(ub, fp.vis_blocks, k=kk,
                                               block_size=rblock)
                seed_fp = prune_fragment_plan(fp, seed_keep)
                sv, _ = bm25_resident_score_topk(
                    put_descriptor_array(seed_fp.desc), w_dev, csc_doc,
                    csc_sc, block_size=rblock, frag=frag, k=kk,
                    n_docs=self.n_docs, double_buffer=False)
                tau = np.asarray(sv)[kk - 1]                 # [B]
                pf = prune_fragment_plan(fp, (ub >= tau[None, :]).any(1))
            else:
                pf = fp
            nf_surv = pf.n_frags
            desc = put_descriptor_array(pf.desc)
            bounds = put_descriptor_array(ub[pf.desc[3]])
        ids, vals, skipped = ops.bm25_retrieve_resident_pruned(
            desc, w_dev, csc_doc, csc_sc, bounds, dids,
            jnp.asarray(shift), block_size=rblock, frag=frag, k=kk,
            n_docs=self.n_docs)
        plan.frags_planned = nf_planned
        plan.frags_pruned = nf_planned - nf_surv
        plan.frags_skipped = int(skipped)
        return ids, vals


# -- deprecated regime aliases -------------------------------------------
#
# The forced-regime subclasses predate ``DeviceRetriever(regime=...)``;
# they add nothing the keyword does not, so they are deprecation shims
# now. Each warns ONCE per process (a fleet constructing thousands of
# shard scorers should not drown its logs), tracked in ``_ALIAS_WARNED``;
# tests reset it via :func:`_reset_alias_warnings`.

_ALIAS_WARNED: set[str] = set()


def _reset_alias_warnings() -> None:
    """Re-arm the once-per-alias deprecation warnings (test hook)."""
    _ALIAS_WARNED.clear()


def _warn_alias(name: str, regime: str) -> None:
    if name in _ALIAS_WARNED:
        return
    _ALIAS_WARNED.add(name)
    warnings.warn(
        f"{name} is deprecated; use DeviceRetriever(index, "
        f"regime={regime!r}) instead",
        DeprecationWarning, stacklevel=3)


class BlockedRetriever(DeviceRetriever):
    """Deprecated alias for ``DeviceRetriever(regime="blocked")``."""

    def __init__(self, index: BM25Index, *, block_size: int = 512,
                 tile: int = 512, q_max: int = 32, **kwargs):
        _warn_alias("BlockedRetriever", "blocked")
        super().__init__(index, regime="blocked", block_size=block_size,
                         tile=tile, q_max=q_max, **kwargs)


class GatheredRetriever(DeviceRetriever):
    """Deprecated alias for ``DeviceRetriever(regime="gathered")``."""

    def __init__(self, index: BM25Index, *, tile: int = 512,
                 acc_block: int = 512, q_max: int = 32, **kwargs):
        _warn_alias("GatheredRetriever", "gathered")
        super().__init__(index, regime="gathered", tile=tile,
                         acc_block=acc_block, q_max=q_max, **kwargs)


class PrunedRetriever(DeviceRetriever):
    """Deprecated alias for ``DeviceRetriever(regime="pruned")``."""

    def __init__(self, index: BM25Index, *, tile: int = 512,
                 q_max: int = 32, **kwargs):
        _warn_alias("PrunedRetriever", "pruned")
        super().__init__(index, regime="pruned", tile=tile, q_max=q_max,
                         **kwargs)


# partials, not the alias classes: engine-internal construction must not
# fire the deprecation warnings users are being migrated off of
_SCORERS = {"scipy": ScipyBM25, "auto": DeviceRetriever,
            "blocked": partial(DeviceRetriever, regime="blocked"),
            "gathered": partial(DeviceRetriever, regime="gathered"),
            "pruned": partial(DeviceRetriever, regime="pruned")}


@dataclass
class ShardRuntime:
    """One shard's scorer (thread-simulated shard server)."""

    index: BM25Index
    delay: Callable[[], float] | None = None     # test hook: seconds to sleep
    scorer: str = "scipy"          # "scipy"|"auto"|"blocked"|"gathered"
    scorer_opts: dict = field(default_factory=dict)  # device-scorer kwargs

    def __post_init__(self):
        if self.scorer not in _SCORERS:
            raise RetrievalConfigError(f"unknown scorer {self.scorer!r}; "
                                       f"available: {sorted(_SCORERS)}")
        self._scorer = _SCORERS[self.scorer](self.index, **self.scorer_opts)

    def health(self) -> dict:
        """Schema-2 health report for this shard (see ``repro.serve``
        package docstring). ``served``/``degraded`` count this shard's
        batches (the scipy reference scorer has no counters — zeros)."""
        sc = self._scorer
        return health_envelope(
            served=getattr(sc, "batches_served", 0),
            degraded=getattr(sc, "batches_degraded", 0),
            faults=dict(getattr(sc, "fault_counters", {})),
            queries=dict(getattr(sc, "query_counters", {})),
            scorer=self.scorer,
            batches_served=getattr(sc, "batches_served", 0),
            batches_degraded=getattr(sc, "batches_degraded", 0),
            degradations=dict(getattr(sc, "degradation_counts", {})),
            snapshot=dict(
                getattr(getattr(sc, "dindex", None), "snapshot_report",
                        None)
                or getattr(self.index, "snapshot_report", None) or {}),
        )

    def warmup(self, k: int) -> None:
        """Pre-compile the device scorer so query #1 skips compilation."""
        fn = getattr(self._scorer, "warmup", None)
        if fn is not None:
            fn(k=k)

    def topk(self, query_tokens: np.ndarray, k: int
             ) -> tuple[np.ndarray, np.ndarray]:
        if self.delay is not None:
            time.sleep(self.delay())
        return self._scorer.retrieve(query_tokens, k)

    def topk_batch(self, query_batch: Sequence[np.ndarray], k: int
                   ) -> tuple[np.ndarray, np.ndarray]:
        """[B queries] -> (ids [B, k'], scores [B, k']) for this shard."""
        if self.delay is not None:
            time.sleep(self.delay())
        fn = getattr(self._scorer, "retrieve_batch", None)
        if fn is not None:                       # one kernel launch for B
            return fn(query_batch, k)
        parts = [self._scorer.retrieve(q, k) for q in query_batch]
        kk = min((p[0].size for p in parts), default=0)
        ids = np.stack([p[0][:kk] for p in parts]) if parts else \
            np.zeros((0, 0), np.int64)
        sc = np.stack([p[1][:kk] for p in parts]) if parts else \
            np.zeros((0, 0), np.float32)
        return ids.astype(np.int64), sc.astype(np.float32)


def _same_shard(a: BM25Index, b: BM25Index) -> bool:
    """Byte-identical postings, doc range AND shift vector — safe to keep
    the resident device arrays of ``a``'s runtime for ``b``. ``doc_lens``
    must match too: a boundary moving through posting-less documents
    changes the shard's doc range without changing a single posting, and
    reusing the old runtime would then serve documents a neighbor shard
    now owns (duplicate results after the merge)."""
    return a is b or (
        int(a.doc_offset) == int(b.doc_offset)
        and np.array_equal(a.doc_lens, b.doc_lens)
        and np.array_equal(a.indptr, b.indptr)
        and np.array_equal(a.doc_ids, b.doc_ids)
        and np.array_equal(a.scores, b.scores)
        and np.array_equal(a.nonoccurrence, b.nonoccurrence))


class RetrievalEngine:
    def __init__(self, shards: Sequence[BM25Index], *, k: int = 10,
                 deadline_s: float = 0.5, quorum: float = 0.75,
                 max_workers: int = 8,
                 delay: Callable[[int], Callable[[], float] | None] = None,
                 scorer: str = "scipy", warmup: bool = True,
                 scorer_opts: dict | None = None,
                 device_indexes: Sequence | None = None):
        self.k = k
        self.deadline_s = deadline_s
        self.quorum = quorum
        self.scorer = scorer
        self.scorer_opts = dict(scorer_opts or {})
        self.warmup = warmup
        self._delay_factory = delay
        self._pool = ThreadPoolExecutor(max_workers=max_workers)
        self.query_counters: dict[str, int] = {}
        self._responses = 0
        self._degraded_responses = 0
        # pre-built per-shard DeviceIndexes (snapshot cold-start via
        # ``RetrievalEngine.load``) — adopted by the FIRST build only;
        # rescale re-buckets postings, so loaded runtimes can't outlive it
        self._adopt = list(device_indexes or [])
        if self._adopt and len(self._adopt) != len(shards):
            raise RetrievalConfigError(
                f"device_indexes has {len(self._adopt)} entries for "
                f"{len(shards)} shards")
        self._build_runtimes(list(shards))

    def _build_runtimes(self, shards: list[BM25Index]) -> None:
        """(Re)build shard runtimes, REUSING any whose postings didn't move.

        Rescale re-uploads only the shards whose postings changed: a
        runtime whose index is byte-identical to a new shard keeps its
        device-resident arrays and compiled-fn cache (no re-upload, no
        re-warmup). ``last_build_stats`` records the split — a same-count
        rescale reuses everything, a boundary-moving one rebuilds only the
        moved shards.
        """
        from ..sparse.block_csr import DeviceIndex
        old = list(getattr(self, "runtimes", []))
        pool: dict[tuple, list[ShardRuntime]] = {}
        for rt in old:
            key = (int(rt.index.doc_offset), int(rt.index.doc_ids.size))
            pool.setdefault(key, []).append(rt)
        runtimes, reused, blockmax_reused = [], 0, 0
        for i, s in enumerate(shards):
            delay = self._delay_factory(i) if self._delay_factory else None
            cands = pool.get((int(s.doc_offset), int(s.doc_ids.size)), [])
            hit = next((rt for rt in cands if _same_shard(rt.index, s)),
                       None)
            if hit is not None:
                cands.remove(hit)
                hit.delay = delay
                runtimes.append(hit)
                reused += 1
                continue
            opts = self.scorer_opts
            if self.scorer != "scipy":
                # incremental re-blocking: a boundary that moved through
                # posting-LESS documents changes a shard's doc range but
                # not one posting byte — the runtime cannot be reused
                # wholesale (global ids shift), but its resident layouts
                # and block-max table can (they depend only on the local
                # postings), so the rebuild re-uploads nothing
                donor = next(
                    (rt for rt in old
                     if getattr(rt._scorer, "dindex", None) is not None
                     and DeviceIndex._postings_identical(s, rt.index)),
                    None)
                if donor is not None:
                    opts = {**opts, "reuse_from": donor._scorer.dindex}
                if i < len(self._adopt) and self._adopt[i] is not None:
                    opts = {**opts, "device_index": self._adopt[i]}
            rt = ShardRuntime(s, delay=delay, scorer=self.scorer,
                              scorer_opts=opts)
            di = getattr(rt._scorer, "dindex", None)
            if di is not None and di.reused and (
                    di.reused.get("bmax") or di.reused.get("blocked")):
                blockmax_reused += 1
            if self.warmup:
                # compile the device scorers at BUILD time (and after every
                # rescale) so the first live query never pays jit
                # compilation — on the floor buckets, which absorb typical
                # traffic.
                rt.warmup(self.k)
            runtimes.append(rt)
        self.shards = shards
        self.runtimes = runtimes
        self._adopt = []                  # adoption is first-build-only
        self.last_build_stats = {"reused": reused,
                                 "built": len(shards) - reused,
                                 "blockmax_reused": blockmax_reused}

    # -- control plane ------------------------------------------------------
    def rescale(self, n_shards: int) -> None:
        """Elastic re-shard (device pool grew or shrank)."""
        self._build_runtimes(reshard_index(self.shards, n_shards))

    ENGINE_FORMAT = "repro-bm25s-engine"
    ENGINE_VERSION = 1

    def save(self, path: str, *, algo: str | None = None) -> dict:
        """Snapshot every shard runtime + the engine config under ``path``.

        Layout: ``engine.json`` (config, written last — tmp + fsync +
        ``os.replace``) next to one ``shard-NNNN/`` snapshot root per
        runtime, each an atomic generation store (see ``sparse.snapshot``).
        Device runtimes persist their resident layouts
        (``save_device_index``: padded CSC + blocked + block-max, every
        file memmap-able); scipy runtimes persist the bare index
        (``save_index``). Re-saving into the same path adds a generation
        per shard and rewrites ``engine.json`` — a crash mid-save leaves
        every shard's previous generation committed.
        """
        import json
        import os

        from ..sparse import snapshot
        os.makedirs(path, exist_ok=True)
        for i, rt in enumerate(self.runtimes):
            sdir = os.path.join(path, f"shard-{i:04d}")
            di = getattr(rt._scorer, "dindex", None)
            if di is not None:
                snapshot.save_device_index(di, sdir,
                                           index=rt._scorer.index,
                                           algo=algo)
            else:
                snapshot.save_index(rt.index, sdir, algo=algo)
        body = {"format": self.ENGINE_FORMAT,
                "version": self.ENGINE_VERSION,
                "n_shards": len(self.runtimes), "k": self.k,
                "deadline_s": self.deadline_s, "quorum": self.quorum,
                "scorer": self.scorer}
        data = json.dumps(body, indent=1, sort_keys=True).encode("utf-8")
        tmp = os.path.join(path, "engine.json.tmp")
        with open(tmp, "wb") as fh:
            fh.write(data)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, os.path.join(path, "engine.json"))
        return body

    @classmethod
    def load(cls, path: str, *, mmap: bool = False,
             host_arrays: str = "keep", verify: bool = True, corpus=None,
             **kwargs) -> "RetrievalEngine":
        """Cold-start an engine from :meth:`save` — no shard rebuilds.

        Device shards come back through ``sparse.snapshot
        .load_device_index`` (checksummed read, memmap when ``mmap=True``,
        resident arrays uploaded straight from the files) and are ADOPTED
        by their runtimes via ``device_index=`` — ``DeviceIndex.build``
        never runs. Scipy shards come back through ``load_index``.
        ``corpus`` (the full tokenized corpus) arms the last recovery
        rung: each shard slices its own document range out of it.
        ``kwargs`` override the saved engine config
        (``RetrievalEngine.__init__`` keywords).
        """
        import json
        import os

        from ..sparse import snapshot
        with open(os.path.join(path, "engine.json"),
                  encoding="utf-8") as fh:
            cfg = json.load(fh)
        if cfg.get("format") != cls.ENGINE_FORMAT:
            from .errors import SnapshotVersionError
            raise SnapshotVersionError(
                f"{path}: not a {cls.ENGINE_FORMAT} store "
                f"(format={cfg.get('format')!r})")
        v = cfg.get("version")
        if not isinstance(v, int) or not 1 <= v <= cls.ENGINE_VERSION:
            from .errors import SnapshotVersionError
            raise SnapshotVersionError(
                f"{path}: engine store version {v!r} not supported")
        scorer = kwargs.pop("scorer", cfg["scorer"])
        opts = dict(k=cfg["k"], deadline_s=cfg["deadline_s"],
                    quorum=cfg["quorum"])
        opts.update(kwargs)
        shards, dis = [], []
        for i in range(int(cfg["n_shards"])):
            sdir = os.path.join(path, f"shard-{i:04d}")
            # corpus is the FULL corpus — each shard's loader slices its
            # own manifest-recorded doc range with global stats
            if scorer == "scipy":
                shards.append(snapshot.load_index(sdir, mmap=mmap,
                                                  verify=verify,
                                                  corpus=corpus))
            else:
                di = snapshot.load_device_index(sdir, mmap=mmap,
                                                host_arrays=host_arrays,
                                                verify=verify,
                                                corpus=corpus)
                host = di.host
                perm = getattr(di, "perm", None)
                if perm is not None and host is not None:
                    # engine shards stay in CLIENT doc order — rescale's
                    # reshard_index and the shard-reuse keys operate on
                    # global client ids; the adopted DeviceIndex keeps
                    # its permuted host for the retriever
                    from ..sparse.reorder import unpermute_index
                    host = unpermute_index(host, perm)
                shards.append(host)
                dis.append(di)
        return cls(shards, scorer=scorer,
                   device_indexes=dis if dis else None, **opts)

    def health(self) -> dict:
        """One operational snapshot of the engine's fault surface.

        Fields (see ROADMAP "Fault tolerance"):

        Schema-2 envelope (see ``repro.serve`` package docstring):
        ``served``/``degraded`` count scatter-gather rounds, and how many
        missed shards (quorum+deadline hedging); ``faults`` aggregates
        the per-shard typed-fault counts; ``queries`` are the
        engine-boundary sanitizer counters. Engine extras:

        * ``responses`` / ``degraded_responses`` — legacy spellings of
          ``served`` / ``degraded``;
        * ``build`` — the last ``_build_runtimes`` reuse split;
        * ``shards`` — per-shard :meth:`ShardRuntime.health`: ladder
          degradation counts keyed ``"from->to"``, typed-fault counts
          keyed by error class, and the shard's own sanitizer counters.
        """
        shard_reports = [rt.health() for rt in self.runtimes]
        return health_envelope(
            served=self._responses,
            degraded=self._degraded_responses,
            faults=merge_fault_counts(shard_reports),
            queries=self.query_counters,
            responses=self._responses,
            degraded_responses=self._degraded_responses,
            build=dict(self.last_build_stats),
            shards=shard_reports,
        )

    # -- data plane ----------------------------------------------------------
    def _scatter_gather(self, submit, merge, k: int):
        """Shared hedged scatter-gather: quorum + deadline + merge, as the
        ``engine.fanout`` and ``engine.merge`` spans of one batch."""
        with obs.batch():
            t0 = obs.now_ns()
            with obs.span("engine.fanout"):
                futures = {submit(rt): i
                           for i, rt in enumerate(self.runtimes)}
                need = max(1, int(np.ceil(self.quorum
                                          * len(self.runtimes))))
                done: dict[int, tuple[np.ndarray, np.ndarray]] = {}
                pending = set(futures)
                while pending:
                    timeout = self.deadline_s - (obs.now_ns() - t0) / 1e9
                    if timeout <= 0 and len(done) >= need:
                        break             # quorum met, deadline passed
                    finished, pending = wait(
                        pending, timeout=max(timeout, 0.005),
                        return_when=FIRST_COMPLETED)
                    for f in finished:
                        done[futures[f]] = f.result()
                    if not finished and len(done) >= need:
                        break
                for f in pending:         # backfill continues off-path
                    f.cancel()
            with obs.span("engine.merge"):
                ids, scores = merge(done.values(), k)
            latency = (obs.now_ns() - t0) / 1e9
        degraded = len(done) < len(self.runtimes)
        self._responses += 1
        self._degraded_responses += int(degraded)
        return RetrievalResult(
            ids=ids, scores=scores, degraded=degraded,
            shards_answered=len(done), latency_s=latency,
            timings={"total_s": latency})

    def _sanitize(self, query_batch):
        """Engine-boundary pass of the shared sanitizer — covers scipy
        runtimes (which have no device-scorer validation of their own)."""
        from ..core.retrieval import validate_query_batch
        n_vocab = self.shards[0].n_vocab if self.shards else 0
        return validate_query_batch(query_batch, n_vocab,
                                    counters=self.query_counters)

    def retrieve(self, query_tokens: np.ndarray, *, k: int | None = None
                 ) -> RetrievalResult:
        k = k or self.k
        query_tokens = self._sanitize([query_tokens])[0]
        return self._scatter_gather(
            lambda rt: self._pool.submit(rt.topk, query_tokens, k),
            self._merge, k)

    def retrieve_batch(self, query_batch: Sequence[np.ndarray], *,
                       k: int | None = None) -> RetrievalResult:
        """B queries in one hedged scatter-gather round.

        Each shard serves the whole batch in ONE device launch
        (``ShardRuntime.topk_batch``), so kernel-launch and query-table
        costs amortize over B; the merge is the batched stage-2
        (``core.retrieval.merge_topk_batch``). Returns a single
        :class:`RetrievalResult` with ``ids``/``scores`` of shape [B, k].
        """
        k = k or self.k
        query_batch = self._sanitize(query_batch)
        return self._scatter_gather(
            lambda rt: self._pool.submit(rt.topk_batch, query_batch, k),
            self._merge_batch, k)

    @staticmethod
    def _merge(parts, k: int) -> tuple[np.ndarray, np.ndarray]:
        # stage-2 of the paper's two-stage top-k, vectorized in
        # core.retrieval.merge_topk (concatenate + argpartition).
        return merge_topk(parts, k)

    @staticmethod
    def _merge_batch(parts, k: int) -> tuple[np.ndarray, np.ndarray]:
        from ..core.retrieval import merge_topk_batch
        return merge_topk_batch(parts, k)
