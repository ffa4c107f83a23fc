"""Top-k selection and the sharded retrieval step.

The paper's §2 "Top-k selection": average-O(n) partition-based selection
(np.argpartition) or JAX/XLA ``top_k`` — it observes the JAX path is faster
in practice, so that is our device default.

At pod scale the corpus is document-sharded; top-k generalizes losslessly to
a two-stage merge: per-shard local top-k (each shard's winners are a superset
of its contribution to the global winners), all-gather the ``k`` candidates
per shard (tiny: ``shards × k × 8B``), then a global top-k over
``shards × k``. ``sharded_retrieve`` expresses this with ``shard_map`` so the
same code runs on 1 device (tests) and 512 chips (dry-run).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .scoring import DeviceIndex, score_query


# -- retrieval planner (cost model over the two device regimes) --------------
#
# The full-scan regime streams EVERY posting tile: O(nnz) per batch, perfect
# locality, zero descriptor work. The gathered regime touches only the
# batch's posting runs: O(Σ df) plus per-run overhead (descriptor build,
# fragment padding, candidate bookkeeping). Both costs are known BEFORE any
# kernel runs — Σ df comes from the host descriptor table (O(U) adds), nnz
# is index metadata — so the regime choice is a free host-side comparison of
#
#     work_ratio = nnz / Σ df(batch uniq tokens)   vs   CROSSOVER
#
# CROSSOVER folds the gathered path's per-posting overhead factor into one
# constant: at work_ratio == CROSSOVER the two regimes break even, above it
# the gather's asymptotic advantage dominates. The default below is
# calibrated from the BENCH_3 sweep (benchmarks/planner.py), which measures
# both forced regimes across corpus-size × df-profile cells and reports the
# implied break-even band; re-calibrate on TPU by re-running
# ``python -m benchmarks.planner`` there and copying the suggested value.

DEFAULT_CROSSOVER = 2.0

# With DEVICE-side fragment planning (``sparse.fragment_device``) the
# gathered regime no longer pays the per-batch O(Σ df) host descriptor walk
# or the descriptor upload — the fixed overhead CROSSOVER folds in shrinks,
# so the break-even moves TOWARD the gather. The discount below scales the
# default crossover when the caller plans on device; like the crossover
# itself it is a calibration constant — re-measure on TPU with
# ``python -m benchmarks.planner`` after kernel/schedule changes.
DEVICE_PLAN_DISCOUNT = 0.75

# The PRUNED regime executes the gathered machinery over only the fragments
# whose block-max upper bound can still beat the top-k threshold — its
# modeled cost is the gathered cost scaled by the estimated surviving-work
# fraction, DIVIDED by this discount: the survivor estimate is discounted
# for the fixed overhead pruning adds (the bound matmul, the seed pass that
# certifies the threshold, and the re-scored seed blocks), so pruning must
# be expected to cut at least (1 - PRUNE_DISCOUNT) of the gathered work
# before the planner will pick it. Calibrate from the BENCH_4 pruned cells
# (``python -m benchmarks.planner`` — re-run ON TPU; the suggested
# procedure is in ROADMAP's three-regime section).
PRUNE_DISCOUNT = 0.5


@dataclass
class RetrievalPlan:
    """One batch's regime decision plus the evidence it was made on.

    The ``frags_*`` counters are filled in by the executing retriever
    (zero until then): ``frags_planned`` is the batch's full fragment
    count, ``frags_pruned`` how many the pre-launch threshold compaction
    removed, ``frags_skipped`` how many more the in-kernel scoreboard test
    skipped mid-launch.

    ``degradations`` is the batch's fallback trail: one entry per ladder
    hop the executing retriever was forced to take (empty on the healthy
    path), each a dict ``{"from", "to", "error", "detail"}`` — see the
    ROADMAP "Fault tolerance" section for the hop order.
    """

    regime: str             # "blocked" | "gathered" | "pruned"
    sum_df: int             # Σ df over the batch's unique tokens
    nnz: int                # the shard's posting count (full-scan work)
    work_ratio: float       # nnz / max(sum_df, 1)
    crossover: float        # threshold used
    forced: bool            # True when the operator pinned the regime
    plan: str = "host"      # where the fragment table is built
    survivor_frac: float | None = None  # pruning-work estimate fed to auto
    frags_planned: int = 0
    frags_pruned: int = 0
    frags_skipped: int = 0
    degradations: list = field(default_factory=list)


def plan_retrieval(sum_df: int, nnz: int, *, regime: str = "auto",
                   crossover: float | None = None,
                   plan: str = "host",
                   survivor_frac: float | None = None) -> RetrievalPlan:
    """Pick full-scan vs gathered vs pruned for one batch (free — no
    device work).

    ``regime="blocked"``/``"gathered"``/``"pruned"`` force that regime
    (the plan still records the evidence, so forced decisions stay
    debuggable); ``"auto"`` compares modeled per-batch costs:

    * blocked   — ``nnz`` (stream every posting tile);
    * gathered  — ``crossover × Σ df`` (the crossover folds the gather's
      per-posting overhead into one constant, so the old rule "gathered
      iff work ratio ≥ crossover" is exactly this cost comparison);
    * pruned    — the gathered cost × ``survivor_frac / PRUNE_DISCOUNT``
      (only when the caller supplies ``survivor_frac``, its block-max
      estimate of the surviving work fraction): pruning pays bound +
      seed-pass overhead, so the estimate must undercut
      :data:`PRUNE_DISCOUNT` before pruning is worth it.

    A batch with no postings at all is trivially gathered (nothing to
    scan beats scanning everything). Cost ties keep the previous regime
    ordering (gathered beats blocked at equality, matching the pre-pruned
    planner exactly when ``survivor_frac`` is None).

    ``plan="device"`` records that the fragment table is built on device —
    its descriptor-build cost is then free on the host, so the DEFAULT
    crossover is scaled by :data:`DEVICE_PLAN_DISCOUNT` (an explicit
    ``crossover`` is always used verbatim).
    """
    if regime not in ("auto", "blocked", "gathered", "pruned"):
        raise ValueError(f"unknown regime {regime!r}")
    if plan not in ("host", "device"):
        raise ValueError(f"unknown plan mode {plan!r}")
    if crossover is None:
        c = DEFAULT_CROSSOVER * (DEVICE_PLAN_DISCOUNT if plan == "device"
                                 else 1.0)
    else:
        c = float(crossover)
    ratio = nnz / max(sum_df, 1)
    if regime != "auto":
        chosen, forced = regime, True
    elif sum_df == 0:
        chosen, forced = "gathered", False
    else:
        costs = {"gathered": c * sum_df, "blocked": float(nnz)}
        if survivor_frac is not None:
            costs["pruned"] = (c * sum_df * float(survivor_frac)
                               / PRUNE_DISCOUNT)
        # first-listed wins ties: gathered over blocked (the pre-pruned
        # rule), either existing regime over pruned (cheaper machinery)
        chosen = min(costs, key=lambda r: (costs[r],
                                           list(costs).index(r)))
        forced = False
    return RetrievalPlan(regime=chosen, sum_df=int(sum_df), nnz=int(nnz),
                         work_ratio=float(ratio), crossover=c,
                         forced=forced, plan=plan,
                         survivor_frac=survivor_frac)


def validate_query_batch(query_tokens, n_vocab: int, *,
                         counters: dict | None = None,
                         on_invalid: str = "sanitize") -> list[np.ndarray]:
    """The ONE query sanitizer every retriever entry point shares.

    Client batches arrive ragged and occasionally malformed; the kernels
    downstream assume clean int32 token ids in ``[0, n_vocab)``. This
    normalizes each entry to a 1-D int32 array, handling:

    * ``None`` / empty entries        -> empty queries (scored as such);
    * multi-dimensional arrays        -> raveled (``_pack_batch`` did this
      silently already; now it is counted);
    * float dtypes with integral data -> recast (dtype drift from JSON or
      feature pipelines);
    * non-integral floats / NaN       -> those tokens dropped;
    * out-of-range / negative ids     -> those tokens dropped.

    Every repair increments ``counters`` (keys ``dropped_tokens``,
    ``recast_queries``, ``raveled_queries``, ``null_queries``) so engine
    ``health()`` reports can expose a misbehaving client instead of
    silently absorbing it. ``on_invalid="raise"`` surfaces
    :class:`repro.serve.errors.InvalidQueryError` on the FIRST defect
    instead of repairing (strict serving mode). Exactness: dropping a
    token the index cannot score is the only behavior-preserving repair —
    a valid token is never altered, so sanitized results equal the
    results on the valid sub-batch exactly.
    """
    if on_invalid not in ("sanitize", "raise"):
        raise ValueError(f"unknown on_invalid mode {on_invalid!r}")
    c = counters if counters is not None else {}

    def bump(key, n=1):
        c[key] = c.get(key, 0) + n

    def bad(msg):
        from repro.serve.errors import InvalidQueryError
        raise InvalidQueryError(msg)

    out = []
    for i, q in enumerate(query_tokens):
        if q is None:
            if on_invalid == "raise":
                bad(f"query {i} is None")
            bump("null_queries")
            out.append(np.zeros(0, np.int32))
            continue
        a = np.asarray(q)
        if a.ndim != 1:
            if on_invalid == "raise" and a.ndim > 1:
                bad(f"query {i} has shape {a.shape}; expected 1-D token ids")
            if a.ndim > 1:
                bump("raveled_queries")
            a = a.ravel()
        if a.dtype.kind == "f":
            finite = np.isfinite(a)
            integral = finite & (a == np.floor(a))
            if not integral.all():
                if on_invalid == "raise":
                    bad(f"query {i} has non-integral or non-finite "
                        f"token ids (dtype {a.dtype})")
                bump("dropped_tokens", int((~integral).sum()))
                a = a[integral]
            if on_invalid == "raise" and a.dtype.kind == "f":
                # integral float batches are recoverable drift, allowed
                # even in strict mode — only lossy repairs raise
                pass
            bump("recast_queries")
            a = a.astype(np.int64)
        elif a.dtype.kind == "b":
            bump("recast_queries")
            a = a.astype(np.int64)
        elif a.dtype.kind not in ("i", "u"):
            if on_invalid == "raise":
                bad(f"query {i} has non-numeric dtype {a.dtype}")
            bump("dropped_tokens", int(a.size))
            a = np.zeros(0, np.int64)
        ok = (a >= 0) & (a < n_vocab)
        if not ok.all():
            if on_invalid == "raise":
                lo = int(a.min()) if a.size else 0
                hi = int(a.max()) if a.size else 0
                bad(f"query {i} token ids must be in [0, {n_vocab}); "
                    f"got range [{lo}, {hi}]")
            bump("dropped_tokens", int((~ok).sum()))
            a = a[ok]
        out.append(a.astype(np.int32, copy=False))
    return out


def default_doc_ids(vis_blocks: np.ndarray, k: int, n_docs: int,
                    block_size: int) -> np.ndarray:
    """First ``k`` doc ids from blocks a batch never visited.

    The resident kernel only scores documents in VISITED blocks; every doc
    in an unvisited block has raw score exactly 0 (no posting touched it),
    so any ``k`` of them serve as the default-document candidates the
    splice needs (mirror of :func:`missing_doc_ids`, but block-granular —
    the fragment plan already knows the visited-block set). Entries ``>=
    n_docs`` mean fewer than ``k`` unvisited docs exist; callers mask them.

    Fully vectorized, O(k log nv) — the j-th-missing trick of
    :func:`missing_doc_ids` applied at block granularity (``vis_blocks``
    is sorted unique, so ``vis[i] - i`` counts the unvisited blocks below
    ``vis[i]``). NOT O(n_blocks) and no per-block Python loop: this sits
    on the resident serving hot path and shards can have 10^5 blocks.
    """
    out = np.full(k, n_docs, dtype=np.int32)
    if k <= 0 or n_docs <= 0:
        return out
    vis = np.asarray(vis_blocks, dtype=np.int64)
    n_blocks = -(-n_docs // block_size)
    # first k unvisited block ids (each supplies ≥1 doc id, so k suffice)
    j = np.arange(min(k, n_blocks), dtype=np.int64)
    unvis = j + np.searchsorted(vis - np.arange(vis.size), j + 1)
    unvis = unvis[unvis < n_blocks]
    if unvis.size == 0:
        return out
    lo = unvis * block_size
    cnt = np.minimum(lo + block_size, n_docs) - lo
    cum = np.cumsum(cnt)
    cut = int(np.searchsorted(cum, k)) + 1        # blocks that reach k ids
    lo, cnt, cum = lo[:cut], cnt[:cut], cum[:cut]
    total = int(cum[-1])
    flat = np.repeat(lo, cnt) + (np.arange(total, dtype=np.int64)
                                 - np.repeat(cum - cnt, cnt))
    take = min(k, total)
    out[:take] = flat[:take].astype(np.int32)
    return out


def topk_numpy(scores: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Paper's np.argpartition path (introspective selection, O(n) average)."""
    k = min(k, scores.shape[-1])
    part = np.argpartition(scores, -k, axis=-1)[..., -k:]
    vals = np.take_along_axis(scores, part, axis=-1)
    order = np.argsort(-vals, axis=-1, kind="stable")
    idx = np.take_along_axis(part, order, axis=-1)
    return idx, np.take_along_axis(scores, idx, axis=-1)


def merge_topk(parts, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-side global merge of per-shard candidate lists (the paper's
    two-stage top-k, stage 2).

    ``parts`` is an iterable of ``(ids, scores)`` arrays — each a shard's
    local top-k. One concatenate + ``argpartition`` (average-O(n) selection)
    replaces the per-candidate Python heap: the candidate count is
    ``shards × k``, tiny, but the vectorized path keeps the serving engine's
    merge off the interpreter even at large fan-in.
    """
    pairs = [(np.asarray(i), np.asarray(s)) for i, s in parts]
    if k <= 0 or not pairs or sum(i.size for i, _ in pairs) == 0:
        return (np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.float32))
    ids = np.concatenate([i.astype(np.int64, copy=False) for i, _ in pairs])
    scores = np.concatenate([s for _, s in pairs]).astype(np.float64,
                                                          copy=False)
    k = min(k, ids.size)
    part = np.argpartition(scores, -k)[-k:]
    order = np.argsort(-scores[part], kind="stable")
    sel = part[order]
    return ids[sel], scores[sel].astype(np.float32)


def merge_topk_batch(parts, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Batched stage-2 merge: per-shard ``(ids [B, k_s], scores [B, k_s])``
    candidate lists -> global ``(ids [B, k], scores [B, k])``.

    The batched counterpart of :func:`merge_topk`: one concatenate along
    the candidate axis + one row-wise ``argpartition`` serves the whole
    query batch — the serving engine's ``retrieve_batch`` merge stays a
    single vectorized pass no matter the fan-in or batch size.
    """
    pairs = [(np.asarray(i), np.asarray(s)) for i, s in parts]
    # batch dim from the materialized pairs — `parts` may be a one-shot
    # iterable and is already consumed by the comprehension above
    b = max((i.shape[0] for i, _ in pairs), default=0)
    pairs = [(i, s) for i, s in pairs if i.size]
    if k <= 0 or not pairs:
        return (np.zeros((b, 0), np.int64), np.zeros((b, 0), np.float32))
    ids = np.concatenate([i.astype(np.int64, copy=False) for i, _ in pairs],
                         axis=1)
    sc = np.concatenate([s for _, s in pairs], axis=1).astype(np.float64,
                                                              copy=False)
    k = min(k, ids.shape[1])
    part = np.argpartition(sc, -k, axis=1)[:, -k:]
    vals = np.take_along_axis(sc, part, axis=1)
    order = np.argsort(-vals, axis=1, kind="stable")
    sel = np.take_along_axis(part, order, axis=1)
    return (np.take_along_axis(ids, sel, axis=1),
            np.take_along_axis(sc, sel, axis=1).astype(np.float32))


def splice_default_docs(cand_vals: jax.Array, cand_ids: jax.Array,
                        candidates: jax.Array, k: int, n_docs: int, *,
                        valid: jax.Array | None = None,
                        doc_limit=None,
                        default_ids: jax.Array | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Merge candidate winners with ``k`` DEFAULT documents per query.

    A document outside the candidate set contributes no posting, so its
    exact raw score is 0 (the §2.1 nonoccurrence shift is a per-query
    constant added later). Those defaults matter whenever a matched doc
    scores *below* zero (robertson IDF) or fewer than ``k`` docs match —
    the full-scan kernel gets this free by touching every doc; here
    :func:`missing_doc_ids` recovers ``k`` non-candidate ids in
    O(k log C) without ever scanning ``n_docs``. The single definition of
    the splice — the host (``ops.bm25_retrieve_gathered``), resident
    (``ops.bm25_retrieve_resident``) and sharded
    (:func:`_device_gathered_topk`) paths must not diverge.

    ``cand_vals``/``cand_ids`` are ``[B, m]`` candidate winners (raw
    scores); ``candidates`` the sorted candidate table with ``valid``
    marking real entries (see :func:`missing_doc_ids`); ``doc_limit``
    (default ``n_docs``, may be traced) masks fabricated ids at/above it
    to -inf — pass the shard's REAL doc count when arrays are padded.
    ``default_ids`` (``[k]``) short-circuits the j-th-missing computation
    when the caller already holds ``k`` known-default ids (the resident
    path's unvisited-block defaults, :func:`default_doc_ids`) —
    ``candidates`` may then be None. Returns ``(ids [B, k], raw values
    [B, k])``.
    """
    if doc_limit is None:
        doc_limit = n_docs
    b = cand_vals.shape[0]
    miss = (missing_doc_ids(candidates, k, n_docs, valid=valid)
            if default_ids is None else default_ids)
    def_v = jnp.where(miss < doc_limit, 0.0,
                      jnp.finfo(cand_vals.dtype).min).astype(cand_vals.dtype)
    all_v = jnp.concatenate(
        [cand_vals, jnp.broadcast_to(def_v[None], (b, k))], axis=1)
    all_i = jnp.concatenate(
        [cand_ids, jnp.broadcast_to(miss[None], (b, k))], axis=1)
    mvals, midx = jax.lax.top_k(all_v, k)
    return jnp.take_along_axis(all_i, midx, axis=-1), mvals


def missing_doc_ids(candidates: jax.Array, k: int, n_docs: int, *,
                    valid: jax.Array | None = None) -> jax.Array:
    """First ``k`` doc ids NOT in a sorted candidate list (the j-th missing
    element trick, O(k log C)).

    ``candidates`` is sorted ascending over its valid prefix; ``valid``
    marks real entries (default: ``candidates >= 0``, matching the
    ``GatheredPostings`` candidate table's -1 padding; the device gather
    passes ``candidates < INT32_MAX`` instead). ``missing_before[i] =
    candidates[i] - i`` counts the doc ids below ``candidates[i]`` that
    are absent; the j-th missing id (0-based) is then
    ``j + searchsorted(missing_before, j + 1)``. Returned entries ``>=
    n_docs`` mean fewer than ``k`` ids are missing — callers mask them.
    """
    if valid is None:
        valid = candidates >= 0
    n = candidates.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    miss_before = jnp.where(valid, candidates - iota, n_docs + 1)
    j = jnp.arange(k, dtype=jnp.int32)
    return j + jnp.searchsorted(miss_before, j + 1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("k",))
def topk_jax(scores: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """XLA top_k (the paper's preferred backend). Returns (indices, values)."""
    vals, idx = jax.lax.top_k(scores, k)
    return idx, vals


def blockwise_topk(scores: jax.Array, k: int, block: int
                   ) -> tuple[jax.Array, jax.Array]:
    """Two-stage single-device top-k: per-block top-k, then merge.

    Lossless: every global winner is a winner of its own block. Average work
    is O(n) + O((n/block)·k log ...) — the distributed merge in miniature,
    and the jnp oracle for ``kernels/blockwise_topk``.
    """
    n = scores.shape[-1]
    assert n % block == 0, (n, block)
    nb = n // block
    kb = min(k, block)
    blocks = scores.reshape(*scores.shape[:-1], nb, block)
    bvals, bidx = jax.lax.top_k(blocks, kb)            # [..., nb, kb]
    base = (jnp.arange(nb, dtype=jnp.int32) * block)[:, None]
    gidx = (bidx + base).reshape(*scores.shape[:-1], nb * kb)
    gvals = bvals.reshape(*scores.shape[:-1], nb * kb)
    mvals, midx = jax.lax.top_k(gvals, min(k, nb * kb))
    return jnp.take_along_axis(gidx, midx, axis=-1), mvals


def _device_gathered_topk(indptr, doc_ids, scores, nonocc, q_tokens,
                          q_weights, n_docs_true, *, p_max: int, k: int,
                          n_docs: int):
    """Shard-local query-driven gather → candidate top-k, all on device.

    The device half of the inverted-index regime (run descriptors computed
    ON DEVICE from the CSC ``indptr`` — no host round-trip inside the
    sharded step):

    1. batch-unique token table (``jnp.unique`` with a static size);
    2. per-token posting-run descriptors ``(start, len)`` from ``indptr``;
    3. one flattened gather of the runs into a static ``p_max`` budget —
       work O(Σ df over batch-unique tokens), shared across the B queries
       instead of per-query like ``score_query``'s ragged gather;
    4. candidate compaction (``jnp.unique`` over gathered doc ids) and a
       segment-sum into a ``[p_max, B]`` candidate accumulator — never
       O(n_docs);
    5. per-query top-k over candidates + default-document splice (a doc
       outside the candidate set scores exactly the §2.1 shift; the j-th
       missing-id trick finds k such ids in O(k log C)).

    ``n_docs`` is the static PADDED per-shard doc count (array sizing);
    ``n_docs_true`` the shard's real count (traced scalar) — the default
    splice only fabricates ids below it, so uneven shards never emit
    phantom padding documents.

    Returns ``(ids [B, kk], scores [B, kk], overflow [] bool)`` with
    ``kk = min(k, n_docs)``; overflow is True iff the batch's posting
    demand exceeded the static ``p_max`` bucket (results are then lower
    bounds — callers retry at a larger bucket). The unique-token table
    needs no overflow flag: its size is min(B·Q, |V|), an upper bound on
    the batch's distinct tokens by construction.
    """
    b, q = q_tokens.shape
    u_max = min(b * q, int(indptr.shape[0]) - 1)
    big = jnp.iinfo(jnp.int32).max
    kk = min(k, n_docs)

    flat_q = jnp.where(q_tokens >= 0, q_tokens, big).reshape(-1)
    uniq = jnp.unique(flat_q, size=u_max, fill_value=big)        # sorted
    valid_u = uniq < big
    safe_u = jnp.where(valid_u, uniq, 0)
    starts = indptr[safe_u]
    lens = jnp.where(valid_u, indptr[safe_u + 1] - starts, 0)    # run descrs

    cum = jnp.cumsum(lens)
    total = cum[-1]
    j = jnp.arange(p_max, dtype=jnp.int32)
    owner = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
    owner = jnp.minimum(owner, u_max - 1)
    off_excl = cum[owner] - lens[owner]
    pos = starts[owner] + (j - off_excl)
    ok = j < total
    g_doc = jnp.where(ok, doc_ids[pos], big)
    g_sc = jnp.where(ok, scores[pos], 0.0)

    # per-query weight column for each unique token (scatter; pads add 0)
    qpos = jnp.clip(jnp.searchsorted(uniq, jnp.where(q_tokens >= 0,
                                                     q_tokens, 0)),
                    0, u_max - 1)
    table = jnp.zeros((u_max, b), scores.dtype).at[
        qpos, jnp.broadcast_to(jnp.arange(b)[:, None], (b, q))
    ].add(q_weights)
    contrib = g_sc[:, None] * jnp.take(table, owner, axis=0)     # [p_max, B]

    # candidate compaction: distinct docs ≤ total ≤ p_max when not
    # overflowing, so c_max = p_max needs no extra overflow condition
    cand = jnp.unique(g_doc, size=p_max, fill_value=big)
    slot = jnp.searchsorted(cand, g_doc).astype(jnp.int32)
    cand_scores = jax.ops.segment_sum(contrib, slot,
                                      num_segments=p_max + 1)[:p_max]
    valid_c = cand < big
    masked = jnp.where(valid_c[:, None], cand_scores,
                       jnp.finfo(cand_scores.dtype).min)
    vals, ci = jax.lax.top_k(masked.T, kk)                       # [B, kk]
    ids = jnp.take(cand, ci)

    # default-doc splice (ids absent from the candidate set, raw score 0);
    # ids at/past the shard's REAL doc count are padding, masked to -inf
    ids, mvals = splice_default_docs(vals, ids, cand, kk, n_docs,
                                     valid=valid_c, doc_limit=n_docs_true)

    valid_qt = q_tokens >= 0
    shift = jnp.sum(jnp.where(valid_qt,
                              nonocc[jnp.where(valid_qt, q_tokens, 0)], 0.0)
                    * q_weights, axis=-1)
    return ids, mvals + shift[:, None], total > p_max


def make_sharded_retrieve(mesh: Mesh, shard_axes: tuple[str, ...], *,
                          p_max: int, k: int, n_docs_per_shard: int,
                          return_overflow: bool = False,
                          gathered: bool = False):
    """Build the pod-scale retrieval step: shard-local score+topk, global merge.

    The device index arrays are sharded over ``shard_axes`` (leading dim =
    shard id); queries are replicated. Returns a jit-able
    ``retrieve(stacked_index, q_tokens[B,Q], q_weights[B,Q])``
    -> (global doc ids [B,k], scores [B,k]). With ``return_overflow=True``
    a third ``[B]`` bool output marks queries whose posting demand exceeded
    ``p_max`` on ANY shard (their scores are lower bounds — mirror of
    ``score_batch(..., return_overflow=True)``).

    ``gathered=True`` swaps the shard-local step for the query-driven
    device gather (:func:`_device_gathered_topk`): posting-run descriptors
    from ``indptr``, one batch-shared gather, candidate-compacted
    accumulation — O(Σ df) instead of a per-query O(p_max)+O(n_docs)
    segment-sum. The overflow flag is then batch-global (the gather is
    batch-shared), broadcast to ``[B]`` for a uniform interface;
    :func:`sharded_retrieve_adaptive` wraps it with larger-bucket retries.
    """
    def local_score_topk(idx_arrays, q_tokens, q_weights):
        # idx_arrays leaves have a leading shard dim of size 1 inside shard_map
        indptr, doc_ids, scores, nonocc, offsets, counts = (
            x[0] for x in idx_arrays)
        if gathered:
            gidx, vals, over = _device_gathered_topk(
                indptr, doc_ids, scores, nonocc, q_tokens, q_weights,
                counts[0], p_max=p_max, k=k, n_docs=n_docs_per_shard)
            gidx = gidx + offsets.astype(jnp.int32)
            over = jnp.broadcast_to(over, (q_tokens.shape[0],))
            return gidx[None], vals[None], over[None]
        dindex = DeviceIndex(indptr, doc_ids, scores, nonocc,
                             n_docs=n_docs_per_shard, doc_offset=0)
        s, over = jax.vmap(
            lambda t, w: score_query(dindex, t, w, p_max=p_max))(
            q_tokens, q_weights)                        # [B, n_local], [B]
        # docs past the shard's REAL count exist only as stacking padding
        # (uneven shards): a padded doc would score the bare nonoccurrence
        # shift and could displace real winners — mask before selecting.
        local = jnp.arange(s.shape[-1], dtype=jnp.int32)
        s = jnp.where(local[None, :] < counts[0], s,
                      jnp.finfo(s.dtype).min)
        vals, local_idx = jax.lax.top_k(s, min(k, n_docs_per_shard))
        gidx = local_idx + offsets.astype(jnp.int32)
        return gidx[None], vals[None], over[None]       # keep shard dim

    spec_idx = tuple(P(shard_axes) for _ in range(6))

    @jax.jit
    def retrieve(idx_arrays, q_tokens, q_weights):
        # check_vma: the gathered step's jnp.unique lowers to a scan whose
        # carry trips shard_map's replication checker on replicated query
        # operands (a checker false positive) — the computation itself is
        # shard-local either way.
        gidx, gvals, gover = shard_map(
            local_score_topk, mesh=mesh,
            in_specs=(spec_idx, P(), P()),
            out_specs=(P(shard_axes), P(shard_axes), P(shard_axes)),
            check_vma=not gathered,
        )(idx_arrays, q_tokens, q_weights)
        # [n_shards, B, k] -> [B, n_shards*k] -> global top-k (the merge)
        b = q_tokens.shape[0]
        allv = jnp.swapaxes(gvals, 0, 1).reshape(b, -1)
        alli = jnp.swapaxes(gidx, 0, 1).reshape(b, -1)
        mvals, midx = jax.lax.top_k(allv, k)
        ids = jnp.take_along_axis(alli, midx, axis=-1)
        if return_overflow:
            return ids, mvals, jnp.any(gover, axis=0)
        return ids, mvals

    return retrieve


def sharded_retrieve_adaptive(mesh: Mesh, shard_axes: tuple[str, ...], *,
                              k: int, n_docs_per_shard: int,
                              p_floor: int = 1024, gathered: bool = True):
    """Adaptive-budget wrapper: overflow becomes a larger-bucket RETRY.

    The static ``p_max`` of :func:`make_sharded_retrieve` silently truncates
    postings when a batch's Σ df exceeds it — score corruption. This wrapper
    sizes the budget as power-of-two buckets starting at ``p_floor`` (one
    compiled variant per bucket, cached here): if the overflow flag fires,
    the batch re-runs at the next bucket until it fits or the bucket covers
    the shard's whole posting array (Σ df ≤ nnz always, so that final
    bucket cannot overflow on the posting budget). Typical traffic settles
    into one bucket after warmup and never recompiles again.

    The retry is CAPPED, not open-ended: if the overflow flag somehow
    persists at the Σdf-covering bucket (which indicates a flag/metadata
    bug, not legitimate demand), the wrapper raises
    :class:`repro.serve.errors.PlanOverflowError` carrying the attempted
    bucket trail instead of returning silently-truncated scores.

    Returns ``retrieve(idx_arrays, q_tokens, q_weights) ->
    (ids [B,k], scores [B,k], p_max_used)``.
    """
    from .scoring import bucket_pow2

    cache: dict[int, object] = {}
    state = {"p": p_floor}    # last successful bucket — the steady state

    def retrieve(idx_arrays, q_tokens, q_weights):
        nnz_pad = int(idx_arrays[1].shape[-1])
        cap = bucket_pow2(nnz_pad, floor=p_floor)
        # start at the last bucket that fit, NOT p_floor: steady-state
        # traffic above the floor must execute ONCE per call, not once per
        # smaller bucket (compilation caching alone doesn't buy that).
        p = min(state["p"], cap)
        attempted = []
        while True:
            fn = cache.get(p)
            if fn is None:
                fn = cache[p] = make_sharded_retrieve(
                    mesh, shard_axes, p_max=p, k=k,
                    n_docs_per_shard=n_docs_per_shard,
                    return_overflow=True, gathered=gathered)
            ids, vals, over = fn(idx_arrays, q_tokens, q_weights)
            attempted.append(p)
            if not bool(np.any(np.asarray(over))):
                state["p"] = p
                return ids, vals, p
            if p >= cap:
                from repro.serve.errors import PlanOverflowError
                raise PlanOverflowError(
                    "posting-budget overflow persists at the Σdf-covering "
                    f"bucket: attempted p_max buckets {attempted} "
                    f"(cap {cap}, shard nnz_pad {nnz_pad}) — the overflow "
                    "flag at the cap indicates corrupt index metadata, "
                    "not query demand", attempted=attempted, cap=cap)
            p = min(p * 2, cap)

    return retrieve


def stack_shard_arrays(shards, mesh: Mesh, shard_axes: tuple[str, ...]):
    """Host → device: stack per-shard index arrays padded to common sizes.

    Returns the 6-tuple consumed by ``make_sharded_retrieve`` with every
    leaf sharded over ``shard_axes`` on its leading (shard) dim, plus the
    static (padded) per-shard doc count. The last leaf carries each
    shard's REAL doc count so the retrieval step can mask the stacking
    padding (uneven shards) instead of scoring phantom documents.
    """
    n = len(shards)
    v = shards[0].n_vocab
    nnz_pad = max(s.doc_ids.size for s in shards)
    ndoc_pad = max(s.doc_lens.size for s in shards)
    indptr = np.zeros((n, v + 1), np.int32)
    doc_ids = np.zeros((n, nnz_pad), np.int32)
    scores = np.zeros((n, nnz_pad), np.float32)
    nonocc = np.zeros((n, v), np.float32)
    offsets = np.zeros((n, 1), np.int32)
    counts = np.zeros((n, 1), np.int32)
    for i, s in enumerate(shards):
        indptr[i] = s.indptr
        doc_ids[i, : s.doc_ids.size] = s.doc_ids
        # padding postings point at doc 0 with score 0 — harmless
        scores[i, : s.scores.size] = s.scores
        nonocc[i] = s.nonoccurrence
        offsets[i, 0] = s.doc_offset
        counts[i, 0] = s.doc_lens.size
    sharding = NamedSharding(mesh, P(shard_axes))
    arrs = tuple(jax.device_put(a, sharding)
                 for a in (indptr, doc_ids, scores, nonocc, offsets, counts))
    return arrs, ndoc_pad
