"""Device-side fragment planning: the batch's DMA-unit table built on TPU.

``sparse.block_csr.fragment_plan`` compiles a query batch into the resident
kernel's ``[6, nf_pad]`` descriptor table by walking the HOST CSC copy —
an O(Σ df) host read per batch, plus a per-batch descriptor upload. This
module is the device port: the SAME table is computed by a jit-compiled
builder straight from the HBM-resident CSC ``indptr``/``doc_ids`` arrays
(:class:`~repro.sparse.block_csr.DeviceIndex`), so steady-state serving
reads no host posting array at all and ships ZERO descriptor bytes
host→device per batch (the table is born on device).

The algorithm mirrors :func:`~repro.sparse.block_csr.fragment_plan`
byte-for-byte (tests assert equality of the emitted tables):

1. posting-run descriptors ``(start, len)`` from the resident ``indptr``
   for the batch's padded unique-token table (sentinel ``INT32_MAX`` rows
   contribute length 0);
2. the flat posting stream is reconstructed positionally over a static
   ``p_bucket`` budget: each position's owning run and posting offset are
   piecewise constant, changing only at the U run offsets, so two prefix
   sums over markers scattered there give both (the run count, and the
   telescoped per-run deltas of ``start - offset``) with no per-position
   lookup; the stream is split into *segments* wherever the owning run or
   the document block of ``doc_ids[pos]`` changes;
3. segments are split into ≤``frag``-sized *fragments* (a cumulative-max
   recovers each position's segment start, so fragment boundaries fall at
   ``frag`` multiples inside every segment), compacted into a static
   ``nf_pad`` table, and stably sorted by document block — identical
   ordering to the host plan because a stable block-sort commutes with
   per-segment fragmenting;
4. the visited-block set (first-fragment-per-block flags after the sort)
   feeds a device port of :func:`~repro.core.retrieval.default_doc_ids`,
   so the default-document splice needs no host plan either.

Static shapes: ``p_bucket`` is pow2-bucketed from the batch's Σ df (free,
host ``df`` metadata — O(V), kept even when the host posting arrays are
dropped); ``nf_pad`` is pow2-bucketed with an OVERFLOW flag — every
fragment carries ≥1 posting, so ``nf ≤ Σ df`` and the retry loop in
:func:`plan_fragments_device` always terminates at the Σ df bucket.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .block_csr import _BOUND_ABS, _BOUND_SLACK, bucket_pow2

_I32_BIG = np.iinfo(np.int32).max


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "frag", "nf_pad", "p_bucket", "k",
                     "n_docs"),
)
def build_fragment_table(uniq: jax.Array, indptr: jax.Array,
                         doc_ids_res: jax.Array, *, block_size: int,
                         frag: int, nf_pad: int, p_bucket: int, k: int,
                         n_docs: int
                         ) -> tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array]:
    """Padded unique tokens × resident CSC -> fragment table, on device.

    ``uniq`` is the ``[U]`` int32 sorted unique-token table padded with
    ``INT32_MAX`` (``pack_query_batch``'s layout — descriptor ``uniq``
    rows index THIS table, matching the kernel's weight rows);
    ``indptr``/``doc_ids_res`` are the resident ``[V+1]`` / ``[1,
    nnz_pad]`` arrays. ``p_bucket`` must cover the batch's Σ df (the
    caller sizes it from host metadata, so it cannot overflow).

    Returns ``(desc [6, nf_pad] i32, def_ids [k] i32, nf [] i32,
    overflow [] bool)``. ``desc`` matches the host
    ``fragment_plan(...).desc`` byte-for-byte whenever ``overflow`` is
    False; ``def_ids`` matches ``default_doc_ids`` on the host plan's
    visited blocks. On overflow (``nf > nf_pad``) the table is garbage —
    callers must retry at a larger bucket.
    """
    u = uniq.shape[0]
    iota_p = jnp.arange(p_bucket, dtype=jnp.int32)
    iota_f = jnp.arange(nf_pad, dtype=jnp.int32)

    # 1. run descriptors from the resident indptr (sentinel rows: len 0)
    valid_u = uniq < _I32_BIG
    safe_u = jnp.where(valid_u, uniq, 0)
    starts = indptr[safe_u]
    lens = jnp.where(valid_u, indptr[safe_u + 1] - starts, 0)

    # 2. flat stream positions + (owner run, doc block) per position.
    # Run u covers [off[u], off[u] + lens[u]); owner and start - offset
    # change only at those offsets, so each is a prefix sum of markers
    # there. An empty run shares its successor's offset and its delta
    # telescopes away; offsets at p_bucket (Σ df fills the bucket) drop.
    cum = jnp.cumsum(lens)
    total = cum[u - 1]
    off = cum - lens
    d = starts - off
    owner = jnp.cumsum(jnp.zeros((p_bucket,), jnp.int32).at[off[1:]].add(
        1, mode="drop"))
    step = d - jnp.concatenate([jnp.zeros((1,), jnp.int32), d[:-1]])
    pos = iota_p + jnp.cumsum(jnp.zeros((p_bucket,), jnp.int32).at[off].add(
        step, mode="drop"))
    ok = iota_p < total
    blk = jnp.where(ok, doc_ids_res[0, jnp.where(ok, pos, 0)] // block_size,
                    _I32_BIG)

    # segment boundaries: owner or block changes (flat order, like host)
    prev_owner = jnp.concatenate(
        [jnp.full((1,), -1, jnp.int32), owner[:-1]])
    prev_blk = jnp.concatenate([jnp.full((1,), -1, jnp.int32), blk[:-1]])
    new_seg = ok & ((iota_p == 0) | (owner != prev_owner)
                    | (blk != prev_blk))

    # 3. fragment boundaries: segment starts + frag multiples within one
    seg_start = jax.lax.cummax(jnp.where(new_seg, iota_p, -1))
    new_frag = ok & (new_seg | ((iota_p - seg_start) % frag == 0))
    frank = jnp.cumsum(new_frag.astype(jnp.int32)) - 1
    nf = jnp.sum(new_frag.astype(jnp.int32))
    nf_c = jnp.minimum(nf, nf_pad)
    overflow = nf > nf_pad

    # compact fragment-start flat positions into [nf_pad] (rank scatter;
    # non-boundary positions collide harmlessly on the dropped extra slot)
    slot = jnp.where(new_frag & (frank < nf_pad), frank, nf_pad)
    fs = jnp.full((nf_pad + 1,), p_bucket, jnp.int32).at[slot].min(iota_p)
    fs = fs[:nf_pad]
    freal = iota_f < nf_c
    safe_fs = jnp.where(freal, fs, 0)
    nxt = jnp.where(iota_f + 1 < nf_c,
                    jnp.concatenate([fs[1:],
                                     jnp.full((1,), p_bucket, jnp.int32)]),
                    total)
    f_start = pos[safe_fs]
    f_valid = jnp.where(freal, nxt - fs, 0)
    f_uniq = owner[safe_fs]
    f_blk = jnp.where(freal, blk[safe_fs], _I32_BIG)

    # stable block-sort of flat-order fragments == host's segment sort
    order = jnp.argsort(f_blk)
    o_start, o_valid, o_uniq, o_blk, o_real = (
        f_start[order], f_valid[order], f_uniq[order], f_blk[order],
        freal[order])
    prev_o = jnp.concatenate([jnp.full((1,), -1, jnp.int32), o_blk[:-1]])
    next_o = jnp.concatenate([o_blk[1:], jnp.full((1,), -1, jnp.int32)])
    o_first = o_real & (o_blk != prev_o)
    o_last = o_real & (o_blk != next_o)
    desc = jnp.stack([
        jnp.where(o_real, o_start, 0),
        o_valid,
        jnp.where(o_real, o_uniq, 0),
        jnp.where(o_real, o_blk, 0),
        o_first.astype(jnp.int32),
        o_last.astype(jnp.int32),
    ]).astype(jnp.int32)

    # 4. default doc ids from unvisited blocks (device default_doc_ids):
    # o_first flags are exactly the sorted visited-block set
    n_blocks = max(1, -(-n_docs // block_size))
    nv = jnp.sum(o_first.astype(jnp.int32))
    vrank = jnp.cumsum(o_first.astype(jnp.int32)) - 1
    vslot = jnp.where(o_first & (vrank < nf_pad), vrank, nf_pad)
    vis = jnp.full((nf_pad + 1,), _I32_BIG, jnp.int32).at[vslot].min(o_blk)
    vis = vis[:nf_pad]
    # j-th missing block via the miss-count trick (vis sorted ascending)
    miss_before = jnp.where(iota_f < nv, vis - iota_f, n_blocks + 1)
    m = max(1, min(k, n_blocks))
    jj = jnp.arange(m, dtype=jnp.int32)
    unvis = jj + jnp.searchsorted(miss_before, jj + 1).astype(jnp.int32)
    uvalid = unvis < n_blocks
    lo = jnp.where(uvalid, unvis * block_size, 0)
    cnt = jnp.where(uvalid, jnp.minimum(lo + block_size, n_docs) - lo, 0)
    ccum = jnp.cumsum(cnt)
    tt = jnp.arange(k, dtype=jnp.int32)
    bidx = jnp.minimum(
        jnp.searchsorted(ccum, tt, side="right").astype(jnp.int32), m - 1)
    flat = lo[bidx] + (tt - (ccum[bidx] - cnt[bidx]))
    def_ids = jnp.where(tt < ccum[m - 1], flat, n_docs).astype(jnp.int32)

    return desc, def_ids, nf, overflow


def plan_fragments_device(dindex, uniq_tab, *, sum_df: int, k: int,
                          block_size: int | None = None,
                          nf_bucket: int | None = None,
                          state: dict | None = None):
    """Build a batch's fragment table ON DEVICE, retrying on nf overflow.

    The device counterpart of calling ``fragment_plan`` +
    ``default_doc_ids`` + ``put_descriptor_array``: nothing O(Σ df) is
    read on host and nothing at all is uploaded (the unique-token table is
    query data the batch ships anyway). ``sum_df`` comes free from the
    host ``df`` metadata and sizes the flat-stream budget, so the posting
    dimension can never overflow; the fragment-count bucket starts at an
    estimate (``Σ df/frag`` full fragments + one per live run) — or
    ``nf_bucket``/the last successful bucket in ``state`` — and doubles on
    the overflow flag up to the Σ df bucket, which always fits because
    every fragment carries at least one posting.

    Each attempt is a ``fragments.build`` span (upload and dispatch) and,
    below the Σ df bucket, a ``fragments.overflow_wait`` span: the one
    read-back, of the fragment count ``nf``, which also decides overflow
    (``nf > nf_pad``). The batch record (:mod:`repro.obs`) gets the
    counters ``frag_builds``, ``stream_positions`` (``p_bucket``),
    ``frag_slots`` (``nf_pad``) and, where it was read, ``frags``.

    Returns ``(desc [6, nf_pad] i32 device, def_ids [k] i32 device,
    nf_bucket_used, nf)``; ``nf`` is None where the table was built at
    the Σ df bucket, which always fits, so nothing was read back.
    """
    if dindex.csc_indptr is None or dindex.csc_doc_ids is None:
        from repro.serve.errors import ResidencyError
        raise ResidencyError("device fragment planning needs a resident "
                             "CSC index (DeviceIndex built with "
                             "with_csc=True)")
    # fault-injection site ``plan.fragments_device`` (repro.serve.faults):
    # an armed overflow fault simulates nf-bucket regrowth exhaustion
    import sys
    _f = sys.modules.get("repro.serve.faults")
    if _f is not None and _f.ACTIVE:
        _f.fire("plan.fragments_device")
    block_size = block_size or dindex.block_size
    frag = dindex.frag
    uniq_np = np.asarray(uniq_tab, dtype=np.int32)
    uniq_dev = None
    u = int(uniq_np.shape[0])
    p_bucket = bucket_pow2(max(sum_df, 1), floor=8)
    cap = p_bucket                       # nf ≤ Σ df ≤ p_bucket, always fits
    if nf_bucket is not None:
        nf_pad = min(bucket_pow2(nf_bucket, floor=8), cap)
    else:
        est = 2 * (sum_df // frag) + u + 8
        nf_pad = min(bucket_pow2(est, floor=8), cap)
        if state is not None:
            nf_pad = min(max(nf_pad, state.get("nf", 8)), cap)
    builds, nf = 0, None
    while True:
        with obs.span("fragments.build"):
            if uniq_dev is None:
                uniq_dev = jnp.asarray(uniq_np)
            desc, def_ids, nf_dev, _ = build_fragment_table(
                uniq_dev, dindex.csc_indptr, dindex.csc_doc_ids,
                block_size=block_size, frag=frag, nf_pad=nf_pad,
                p_bucket=p_bucket, k=k, n_docs=dindex.n_docs)
        builds += 1
        if nf_pad >= cap:
            break
        with obs.span("fragments.overflow_wait"):
            nf = int(nf_dev)
        if nf <= nf_pad:
            break
        nf_pad = min(nf_pad * 2, cap)    # overflow -> retry, never truncate
    if state is not None:
        state["nf"] = nf_pad
    obs.count("frag_builds", builds)
    obs.count("stream_positions", p_bucket)
    obs.count("frag_slots", nf_pad)
    if nf is not None:
        obs.count("frags", nf)
    return desc, def_ids, nf_pad, nf


# -- device half of the pruned regime ----------------------------------------
#
# The threshold-aware pruning pass mirrors the host one
# (``block_csr.block_upper_bounds`` / ``prune_fragment_plan`` /
# ``select_seed_blocks``) but reads only the HBM-resident block-max table
# and the device-built fragment table — under ``plan="device"`` the pruned
# regime therefore ships ZERO descriptor bytes host→device per batch, same
# invariant as the unpruned device plan (the compacted table and the bound
# rows are born on device).


@functools.partial(jax.jit, static_argnames=("quantized",))
def block_bounds_device(table: jax.Array, scale: jax.Array, uniq: jax.Array,
                        weights: jax.Array, *, quantized: bool) -> jax.Array:
    """Device port of ``block_csr.block_upper_bounds``: ``[nb_pad, B]``.

    ``table`` is the resident ``[V, nb_pad]`` block-max array (u8 codes
    when ``quantized`` — dequantized here against the ``[V]`` per-token
    ``scale`` vector, ceil-quantization keeps the bound conservative);
    ``uniq``/``weights`` are the batch's packed query operands (sentinel
    rows carry zero weight). Slack-inflated in lockstep with the host
    version so both planners prune identically-safely.
    """
    safe = jnp.clip(uniq.astype(jnp.int32), 0, table.shape[0] - 1)
    rows = table[safe].astype(jnp.float32)               # [U, nb_pad]
    if quantized:
        rows = rows * scale[safe][:, None]
    ub = rows.T @ weights                                # [nb_pad, B]
    return ub * (1.0 + _BOUND_SLACK) + _BOUND_ABS


@jax.jit
def compact_fragment_table(desc: jax.Array, keep: jax.Array
                           ) -> tuple[jax.Array, jax.Array]:
    """Stable-partition a ``[6, nf_pad]`` table to the kept columns.

    Surviving fragments keep their relative order (a stable argsort on the
    drop flag), so the block grouping and first/last accumulator flags
    stay valid as long as ``keep`` is block-uniform — which the threshold
    test guarantees (it depends only on the fragment's block). Dropped
    columns become all-zero padding at the tail. Returns ``(compacted
    [6, nf_pad], n_kept [])``; the caller slices the static width down to
    the survivor bucket (pure device slicing, nothing uploaded).
    """
    order = jnp.argsort(jnp.logical_not(keep), stable=True)
    return (jnp.where(keep[order][None, :], desc[:, order], 0),
            jnp.sum(keep.astype(jnp.int32)))


@functools.partial(jax.jit, static_argnames=("n_seed",))
def seed_fragment_mask(desc: jax.Array, ub: jax.Array, *, n_seed: int
                       ) -> jax.Array:
    """Fragments of each query's ``n_seed`` highest-bound visited blocks.

    The threshold-seeding choice (device port of
    ``block_csr.select_seed_blocks``): PER QUERY, scoring the
    highest-upper-bound blocks first yields a tight per-query threshold;
    the per-query picks are unioned (a shared pick would let one query's
    hot blocks crowd out the rest). Ties at a query's ``n_seed``-th bound
    admit extra blocks — more seed work, never less correctness. Returns
    a block-uniform boolean mask over columns.
    """
    blk = desc[3]
    real = desc[1] > 0
    neg = jnp.finfo(ub.dtype).min
    # per-(block, query) bound restricted to blocks the batch visits
    blk_score = jnp.full(ub.shape, neg, ub.dtype).at[blk].max(
        jnp.where(real[:, None], ub[blk], neg))          # [nb_pad, B]
    kth = jax.lax.top_k(blk_score.T,
                        min(n_seed, ub.shape[0]))[0][:, -1]   # [B]
    kth = jnp.maximum(kth, neg / 2)      # no-visited/padding query: none
    # the zero-bound floor keeps an all-tied trivial column (a real empty
    # query: every block bounds at the additive slack) from seeding the
    # whole table — a zero-bound block cannot tighten any threshold
    live = blk_score[blk] > 2.0 * _BOUND_ABS
    return real & jnp.any((blk_score[blk] >= kth[None, :]) & live, axis=1)


@jax.jit
def prune_fragment_mask(desc: jax.Array, ub: jax.Array, tau: jax.Array
                        ) -> jax.Array:
    """Survivors of the threshold test: blocks some query can still win.

    ``tau`` is the ``[B]`` per-query threshold (a real document's full
    kernel-computed score per query — the seed scoreboard's k-th row — so
    a certified lower bound on each final k-th score; -inf rows disable
    pruning for that query). A fragment survives iff ANY query's bound
    reaches its threshold; the test reads only the fragment's block, so
    the mask is block-uniform and compaction preserves accumulator flags.
    """
    return (desc[1] > 0) & jnp.any(ub[desc[3]] >= tau[None, :], axis=1)
