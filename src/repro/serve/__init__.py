"""Serving stack: sharded retrieval engine with hedging, an async
micro-batching front-end, and the LM decode engine.

The retrieval surface speaks ONE result dialect and ONE health dialect:

**Results.** Every retrieval entry point — ``DeviceRetriever.retrieve`` /
``retrieve_batch``, ``RetrievalEngine.retrieve`` / ``retrieve_batch``,
and the futures ``ServingFrontend.submit`` resolves — returns a
:class:`~repro.serve.results.RetrievalResult` carrying the winner boards
plus the evidence they were produced on (plan, degradation trail,
stage timings). It unpacks as the legacy ``(ids, scores)`` tuple, so
pre-unification call sites keep working unchanged.

**Health — the schema-2 contract.** Every level's ``health()`` —
``DeviceRetriever``, ``ShardRuntime``, ``RetrievalEngine``,
``ServingFrontend`` — returns one envelope
(:func:`~repro.serve.health.health_envelope`) whose COMMON keys mean the
same thing everywhere:

* ``schema``  — the schema version int
  (:data:`~repro.serve.health.HEALTH_SCHEMA`, currently ``2``);
* ``served``  — responses this level completed: batches for a retriever
  or shard, scatter-gather rounds for the engine, client requests for
  the front-end;
* ``degraded`` — how many of those were served degraded: exact-ladder
  hops (retriever/shard), missed shards under quorum+deadline hedging
  (engine), deadline-missed-but-answered requests (front-end). Degraded
  responses are still EXACT — degradation changes cost, never results;
* ``faults``  — typed-fault counts keyed by ``RetrievalError`` subclass
  name, aggregated upward (the engine sums its shards');
* ``queries`` — shared-sanitizer repair counters
  (``core.retrieval.validate_query_batch`` keys, e.g.
  ``clamped_tokens`` / ``dropped_tokens``).

Level-specific extras (legacy spellings like ``batches_served`` /
``responses``, per-shard breakdowns, the front-end's queue/batch stats)
ride alongside the common keys; tooling written against schema 2 reads
only the common ones.

**Overload protection — the contract.** When traffic exceeds capacity or
a ladder rung keeps faulting, the stack sheds and degrades in TYPED,
observable ways; it never queues unboundedly, never hangs a client
future, and never changes scores (every request it does answer is
bit-identical to a direct ``retrieve_batch`` of the same formed batch):

* load above the admission gate is shed at ``submit`` with
  :class:`AdmissionRejectedError` (``retry_after_s`` = backoff hint)
  BEFORE consuming device work, so admitted-request p99 stays bounded
  under sustained overload;
* a rung that faults repeatedly is skipped by a per-rung circuit
  breaker for a cooldown (one half-open probe re-closes it) — the
  ladder keeps serving exactly on the remaining rungs;
* device execution is watchdog-guarded: a stall becomes a typed
  :class:`ExecutionStalledError` feeding the same exact ladder, and
  transient :class:`ResidencyError` gets seeded bounded backoff;
* a dead pipeline stage fails its pending futures with
  :class:`StageFailedError` and restarts (bounded), so clients never
  block on a stage that no longer exists.

Every shed / breaker-open / stall / restart is a ``health()`` counter.
The knobs (all constructor arguments, all off by default except the
breakers):

====================== ========================= =======================
knob                   constructor               default
====================== ========================= =======================
admission_rate_qps     ``ServingFrontend``       None (bucket off)
admission_burst        ``ServingFrontend``       ``max(rate//5, 8)``
codel_target_s         ``ServingFrontend``       None (CoDel off)
codel_interval_s       ``ServingFrontend``       0.1
max_stage_restarts     ``ServingFrontend``       3
watchdog_s             ``DeviceRetriever``       None (watchdog off)
retry_budget           ``DeviceRetriever``       0 (no retries)
retry_backoff_s        ``DeviceRetriever``       0.005
breaker_threshold      ``DeviceRetriever``       3 (None disables)
breaker_window_s       ``DeviceRetriever``       30.0
breaker_cooldown_s     ``DeviceRetriever``       5.0
====================== ========================= =======================

**Batch records — what the server just did.** :mod:`repro.obs` keeps
one record per batch, always on, for the last :data:`repro.obs.RING`
batches: ``repro.obs.batches()`` returns them oldest first, a flight
recorder to read after a slow or failed batch. A record holds spans,
``(name, batch, parent, t0_ns, t1_ns)`` on ``time.perf_counter_ns`` (the
one clock every stage timer reads), and counters:

============================ ============================================
span                         host work it covers
============================ ============================================
``retriever.pack``           fault hook, sanitizer, pow2 pack
                             (``timings["pack_s"]``)
``retriever.retrieve``       everything below (``timings["execute_s"]``)
``retriever.plan``           Σ df, the block-max survivor estimate where
                             it runs, the regime choice
``fragments.build``          one device fragment-table build: upload and
                             dispatch (one per overflow retry)
``fragments.overflow_wait``  host blocked reading the builder's fragment
                             count
``kernel.dispatch``          weight/shift upload and the scoring kernel's
                             dispatch
``board.wait``               host blocked reading the ``[B, k]`` board
``board.finish``             finite check, ids, remap, result assembly
``hop.<rung>``               a ladder rung other than the resident gather
                             (``pruned``, ``host``, ``blocked``,
                             ``oracle``)
``frontend.queue``           one request, submit to its batch's flush
                             (``timings["queue_s"]``)
``frontend.pack_wait``       the batch, flush to pack start
``frontend.exec_wait``       the batch, ready to execute start
``engine.fanout``            shard fan-out up to quorum and deadline
``engine.merge``             the shards' top-k merge
============================ ============================================

Counters: ``sum_df`` (the planner's Σ df), ``frag_builds`` (1 plus
overflow retries), ``stream_positions`` (the builder's pow2 Σ df
bucket), ``frags`` (real fragments) and ``frag_slots`` (the fragment
bucket the kernel runs over). The ``frontend.*`` waits cross threads and
are recorded only; every other span is also a
``jax.profiler.TraceAnnotation``, so a trace taken with
``jax.profiler.start_trace(dir)`` … ``stop_trace()`` (view it in
TensorBoard's profile plugin or Perfetto) shows the spans on the host
threads beside the device ops they dispatch and wait on.
"""

from .errors import (AdmissionRejectedError, DeadlineExceededError,
                     ExecutionStalledError, InvalidQueryError,
                     PlanOverflowError, QueueOverflowError, ResidencyError,
                     RetrievalConfigError, RetrievalError,
                     ScoreIntegrityError, SnapshotIntegrityError,
                     SnapshotVersionError, StageFailedError,
                     TruncationWarning)
from .overload import (AdmissionController, CircuitBreaker, RetryPolicy,
                       WatchdogExecutor)
from .health import HEALTH_SCHEMA, health_envelope
from .results import PackedBatch, RetrievalResult
from .retrieval_engine import (BlockedRetriever, DeviceRetriever,
                               GatheredRetriever, PrunedRetriever,
                               RetrievalEngine, ShardRuntime)
from .frontend import ServingFrontend
from .decode_engine import DecodeEngine

__all__ = ["BlockedRetriever", "DeviceRetriever", "GatheredRetriever",
           "PrunedRetriever", "RetrievalEngine", "ShardRuntime",
           "ServingFrontend", "RetrievalResult", "PackedBatch",
           "HEALTH_SCHEMA", "health_envelope",
           "DecodeEngine", "RetrievalError", "InvalidQueryError",
           "PlanOverflowError", "ResidencyError", "ScoreIntegrityError",
           "RetrievalConfigError", "SnapshotIntegrityError",
           "SnapshotVersionError", "DeadlineExceededError",
           "QueueOverflowError", "AdmissionRejectedError",
           "ExecutionStalledError", "StageFailedError",
           "AdmissionController", "CircuitBreaker", "RetryPolicy",
           "WatchdogExecutor", "TruncationWarning"]
