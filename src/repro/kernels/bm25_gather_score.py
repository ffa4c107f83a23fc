"""Pallas TPU kernel: query-driven gather→score→top-k (the O(Σ df) path).

The fused full-scan kernel (``bm25_block_score_topk``) walks EVERY posting
tile in the shard per query batch — O(nnz) compares and scatters, which
quietly re-introduced the corpus-size dependence the paper's eager scoring
removed. This kernel restores the inverted-index asymptotics on device:

* the host (or a device prologue) slices only the query tokens' posting
  runs out of the CSC layout — O(Σ df(qᵢ)) postings over the batch's
  unique tokens (``sparse.block_csr.gather_posting_runs``);
* gathered postings arrive candidate-compacted: doc ids are mapped to dense
  slots ``0..n_candidates-1`` (sorted-unique order), chunked so each chunk's
  slots fit a ``[acc_block, B]`` VMEM accumulator — the accumulator is sized
  to the *gathered candidate set*, not the shard's document count;
* scoring reuses ``_score_tile``'s membership/one-hot machinery unchanged;
  the final posting tile of each chunk masks padding slots (``candidates ==
  -1``) and runs ``select_topk`` column-wise, translating winning slots back
  to **global doc ids** in-register via the chunk's candidate table — the
  kernel emits ``[n_chunks, k, B]`` (values, global ids) per launch and the
  caller's merge needs no block-offset arithmetic.

Regime choice (see also ``bm25_block_score.py``): full-scan wins when the
query batch is so large/dense that Σ df approaches nnz (every tile would be
gathered anyway — then the streamed layout's perfect locality is free);
query-gathered wins everywhere else, and the gap grows linearly with corpus
size at fixed query df. ``serve.retrieval_engine``'s planner picks per
batch (``core.retrieval.plan_retrieval``, ``scorer="auto"``).

Three gathered entry points:

* ``bm25_gather_score_topk``     — consumes HOST-gathered candidate-compacted
  tiles (the fallback that still ships O(Σ df) postings per batch). With
  ``two_level=True`` the per-chunk winners are reduced to SHARD winners
  inside the launch (running ``[k, B]`` scoreboard in VMEM), cutting the
  host merge from ``[nc·k, B]`` to ``[k, B]``.
* ``bm25_resident_score_topk``   — the zero-copy path: posting arrays are
  HBM-resident (``sparse.block_csr.DeviceIndex``), the host ships only a
  fragment-descriptor table (``fragment_plan``) which streams through
  SMEM in chunks; each grid step DMAs one ≤``frag``-sized
  posting run fragment straight out of HBM, scatters it into a per-doc-block
  VMEM accumulator, and block winners fold into the same running ``[k, B]``
  shard scoreboard. No membership search is needed at all — the descriptor
  names the owning query-token row directly.
* ``bm25_resident_score_topk_pruned`` — the resident path with the
  block-max skip: an extra ``[nf, B]`` bound-row operand (per-fragment
  document-block score upper bounds from the resident
  ``sparse.block_csr.BlockMaxTable``) is tested against the live
  scoreboard's k-th value before each fragment's DMAs are issued, so
  fragments no posting can win are never copied at all — exact top-k
  pruning, bit-identical to the single-buffer kernel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .blockwise_topk import select_topk
from .bm25_block_score import (_score_tile, as_posting_rows, onehot_dot,
                               posting_row_spec)

# HBM tiles the [1, nnz_pad] resident CSC arrays 128 lanes wide, and a DMA
# may only start on a tile boundary
_LANE = 128

# The fragment table drives every grid step's DMAs, so the resident kernels
# read it from SMEM. It streams through in chunks of this many fragments:
# scalar-prefetched whole it overflows the v5e's 1 MiB of SMEM (rows pad
# to 8) past 32k fragments, which one 32-query batch passes at 2^18 docs.
_DESC_CHUNK = 1024


def _desc_spec(nf: int, ahead: int = 0) -> pl.BlockSpec:
    """The SMEM chunk of the ``[6, nf]`` fragment table that holds fragment
    ``i + ahead`` at grid step ``i`` (the last chunk past the end)."""
    chunk = min(nf, _DESC_CHUNK)
    assert nf % chunk == 0, (nf, chunk)          # nf_pad is a power of two
    last = nf // chunk - 1
    return pl.BlockSpec(
        (6, chunk), lambda i: (0, jnp.minimum((i + ahead) // chunk, last)),
        memory_space=pltpu.SMEM)


def _fragment(desc_ref, i):
    """``(start, valid, uniq, block, first, last)`` of fragment ``i`` from
    the SMEM chunk that holds it."""
    c = i % desc_ref.shape[1]
    return tuple(desc_ref[r, c] for r in range(6))


def _fold_winners(ext_vals, ids_of_row, prev_ids, mv_ref, mi_ref, *,
                  n_rows: int, k: int):
    """k select-and-mask rounds over ``ext_vals = [acc ; prev_winners]``.

    ``ids_of_row(am)`` maps an accumulator-row argmax to its global doc id;
    rows ≥ ``n_rows`` are the previous winners, whose ids come from
    ``prev_ids`` via a one-hot sum (VPU-safe — no gather along a dynamic
    per-column index). Non-finite winners (padding) emit id -1. Results are
    staged in ``mv_ref``/``mi_ref`` so the caller can copy them into the
    live scoreboard AFTER the rounds stop reading it.
    """
    neg = jnp.finfo(ext_vals.dtype).min

    def emit(r, m, am):
        b = m.shape[0]
        safe_prev = jnp.clip(am - n_rows, 0, k - 1)
        oh = (jax.lax.broadcasted_iota(jnp.int32, (k, b), 0)
              == safe_prev[None, :])
        old = jnp.sum(jnp.where(oh, prev_ids, 0), axis=0)
        gid = jnp.where(am < n_rows, ids_of_row(am), old)
        gid = jnp.where(m > neg / 2, gid, -1)
        mv_ref[pl.ds(r, 1), pl.ds(0, b)] = m[None, :]
        mi_ref[pl.ds(r, 1), pl.ds(0, b)] = gid[None, :]

    select_topk(ext_vals, k, axis=0, emit=emit)


def _slot_ids(cand, am):
    """``cand[am]`` for a ``[rows, 1]`` id column and ``[B]`` row picks —
    a one-hot select-and-sum (Mosaic lowers no dynamic vector gather)."""
    oh = (jax.lax.broadcasted_iota(jnp.int32, (cand.shape[0], am.shape[0]), 0)
          == am[None, :])
    return jnp.sum(jnp.where(oh, cand, 0), axis=0)


def _gather_kernel(tok_ref, loc_ref, sc_ref, uniq_ref, w_ref, cand_ref,
                   vals_ref, gid_ref, acc_ref, *, acc_block: int, k: int):
    """One (chunk, posting-tile) grid step of the gathered fused path."""
    pj = pl.program_id(1)

    @pl.when(pj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _score_tile(tok_ref, loc_ref, sc_ref, uniq_ref, w_ref,
                                block_size=acc_block)

    @pl.when(pj == pl.num_programs(1) - 1)
    def _reduce():
        acc = acc_ref[...]                                   # [acc_block, B]
        cand = cand_ref[...]                                 # [acc_block, 1]
        # padding slots (no candidate doc) must not outrank real negative
        # scores — same contract as the full-scan kernel's tail-doc mask,
        # but driven by the candidate table instead of a static n_docs.
        acc = jnp.where(cand >= 0, acc, jnp.finfo(acc.dtype).min)

        def emit(i, m, am):                                  # m, am: [B]
            b = m.shape[0]
            gid = _slot_ids(cand, am)                        # slot -> doc id
            vals_ref[pl.ds(0, 1), pl.ds(i, 1), pl.ds(0, b)] = m[None, None, :]
            gid_ref[pl.ds(0, 1), pl.ds(i, 1), pl.ds(0, b)] = gid[None, None, :]

        select_topk(acc, k, axis=0, emit=emit)


def _gather_kernel_shard(tok_ref, loc_ref, sc_ref, uniq_ref, w_ref, cand_ref,
                         vals_ref, gid_ref, acc_ref, mv_ref, mi_ref, *,
                         acc_block: int, k: int):
    """Two-level variant: chunk winners fold into a shard ``[k, B]`` board.

    Same scoring as :func:`_gather_kernel`, but instead of emitting every
    chunk's ``[k, B]`` winners to HBM, each chunk's reduce extends its
    accumulator with the RUNNING shard winners and re-selects — top-k of a
    union equals top-k of (top-k ∪ top-k), so the single ``[k, B]`` output
    is exactly the merge of the per-chunk lists, computed without the
    ``[nc·k, B]`` round-trip.
    """
    pi = pl.program_id(0)
    pj = pl.program_id(1)
    neg = jnp.finfo(vals_ref.dtype).min

    @pl.when((pi == 0) & (pj == 0))
    def _init_out():
        vals_ref[...] = jnp.full_like(vals_ref, neg)
        gid_ref[...] = jnp.full_like(gid_ref, -1)

    @pl.when(pj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _score_tile(tok_ref, loc_ref, sc_ref, uniq_ref, w_ref,
                                block_size=acc_block)

    @pl.when(pj == pl.num_programs(1) - 1)
    def _reduce():
        acc = acc_ref[...]                                   # [acc_block, B]
        cand = cand_ref[...]                                 # [acc_block, 1]
        acc = jnp.where(cand >= 0, acc, neg)
        prev_v, prev_i = vals_ref[...], gid_ref[...]
        ext = jnp.concatenate([acc, prev_v], axis=0)
        _fold_winners(ext, lambda am: _slot_ids(cand, am), prev_i, mv_ref,
                      mi_ref, n_rows=acc_block, k=k)
        vals_ref[...] = mv_ref[...]
        gid_ref[...] = mi_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("acc_block", "k", "tile_p", "two_level", "interpret"),
)
def bm25_gather_score_topk(token_ids: jax.Array, slot_ids: jax.Array,
                           scores: jax.Array, uniq_tokens: jax.Array,
                           weights: jax.Array, candidates: jax.Array, *,
                           acc_block: int, k: int, tile_p: int = 512,
                           two_level: bool = False,
                           interpret: bool | None = None
                           ) -> tuple[jax.Array, jax.Array]:
    """Gathered postings -> (values, GLOBAL doc ids).

    Inputs are the :class:`~repro.sparse.block_csr.GatheredPostings` layout:
    ``[n_chunks, p_pad]`` posting tiles whose ``slot_ids`` index a
    ``[acc_block, B]`` VMEM accumulator, plus the ``[n_chunks, acc_block]``
    candidate table mapping slots back to global doc ids (-1 = pad). Work is
    O(Σ df · B) — independent of both corpus size and total nnz.

    ``two_level=False`` emits per-chunk winners ``[n_chunks, k, B]`` (the
    caller merges). ``two_level=True`` performs that merge INSIDE the
    launch — chunk winners fold into a running shard scoreboard and the
    output is ``[k, B]``, cutting HBM winner traffic and the host merge by
    ``n_chunks``×.
    """
    token_ids, slot_ids, scores = as_posting_rows(token_ids, slot_ids,
                                                  scores)
    nc, _, p = token_ids.shape
    u, b = weights.shape
    assert p % tile_p == 0, (p, tile_p)
    assert k <= acc_block, (k, acc_block)
    assert candidates.shape == (nc, acc_block), (candidates.shape, nc,
                                                 acc_block)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    grid = (nc, p // tile_p)
    in_specs = [
        posting_row_spec(tile_p),                            # token_ids
        posting_row_spec(tile_p),                            # slot_ids
        posting_row_spec(tile_p),                            # scores
        pl.BlockSpec((1, u), lambda i, j: (0, 0)),           # uniq table
        pl.BlockSpec((u, b), lambda i, j: (0, 0)),           # weights
        pl.BlockSpec((None, acc_block, 1),
                     lambda i, j: (i, 0, 0)),                # candidate col
    ]
    operands = (token_ids, slot_ids, scores, uniq_tokens.reshape(1, u),
                weights, candidates.reshape(nc, acc_block, 1))
    if two_level:
        return pl.pallas_call(
            functools.partial(_gather_kernel_shard, acc_block=acc_block,
                              k=k),
            grid=grid,
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((k, b), lambda i, j: (0, 0)),   # shard values
                pl.BlockSpec((k, b), lambda i, j: (0, 0)),   # shard ids
            ),
            out_shape=(
                jax.ShapeDtypeStruct((k, b), weights.dtype),
                jax.ShapeDtypeStruct((k, b), jnp.int32),
            ),
            scratch_shapes=[
                pltpu.VMEM((acc_block, b), weights.dtype),
                pltpu.VMEM((k, b), weights.dtype),
                pltpu.VMEM((k, b), jnp.int32),
            ],
            interpret=interpret,
            name="bm25_gather_score_topk_two_level",
        )(*operands)
    return pl.pallas_call(
        functools.partial(_gather_kernel, acc_block=acc_block, k=k),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, k, b), lambda i, j: (i, 0, 0)),     # values
            pl.BlockSpec((1, k, b), lambda i, j: (i, 0, 0)),     # global ids
        ),
        out_shape=(
            jax.ShapeDtypeStruct((nc, k, b), weights.dtype),
            jax.ShapeDtypeStruct((nc, k, b), jnp.int32),
        ),
        scratch_shapes=[pltpu.VMEM((acc_block, b), weights.dtype)],
        interpret=interpret,
        name="bm25_gather_score_topk",
    )(*operands)


def _dma_window(frag: int) -> tuple[int, int]:
    """``(align, width)`` of the posting window one fragment DMA copies.

    A fragment starts at an arbitrary posting offset, so the kernel copies
    the ``width = frag + align`` postings from the ``align``-aligned
    position at or below it and masks the lanes outside the fragment.
    ``align`` is the lane tile (128) whenever ``frag`` is a multiple of it
    — the resident arrays' ``frag`` tail padding then covers the extra
    lanes — and ``gcd(frag, 128)`` for the small fragments of CPU tests.
    """
    align = math.gcd(frag, _LANE)
    return align, frag + align


def _window_start(start, align: int):
    """Fragment start -> (aligned DMA start, fragment lane offset)."""
    base = pl.multiple_of((start // align) * align, align)
    return base, start - base


def _resident_scatter(acc_ref, w_ref, doc, sc, off, valid, uidx, blk, *,
                      block_size: int, width: int):
    """Scatter one fragment's postings into the block accumulator.

    ``doc``/``sc`` are the fragment's DMA window; its postings are lanes
    ``[off, off + valid)``. The ONE scoring definition shared by the
    single-buffered, double-buffered and pruned resident kernels —
    identical operations in identical order, so the paths are
    bit-identical (the double-buffer test asserts it).
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
    ok = (lane >= off) & (lane < off + valid)            # [W, 1]
    w_row = w_ref[pl.ds(uidx, 1), :]                     # [1, B]
    contrib = jnp.where(ok, sc[:, None], 0.0) * w_row    # [W, B]
    # lanes outside the fragment may carry arbitrary doc ids, but their
    # contrib rows are zero — a spurious one-hot match adds exactly 0.
    # One token's run holds each doc at most once, so every accumulator
    # entry receives at most one nonzero product per fragment.
    loc = doc - blk * block_size
    d_iota = jax.lax.broadcasted_iota(jnp.int32, (block_size, width), 0)
    oneh = (d_iota == loc[None, :]).astype(contrib.dtype)
    acc_ref[...] += onehot_dot(oneh, contrib)            # [BS, B] MXU


def _resident_fold(acc_ref, vals_ref, gid_ref, mv_ref, mi_ref, blk, *,
                   block_size: int, k: int, n_docs: int):
    """Fold a finished block accumulator into the shard scoreboard."""
    neg = jnp.finfo(vals_ref.dtype).min
    acc = acc_ref[...]                                   # [BS, B]
    row = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    acc = jnp.where(blk * block_size + row < n_docs, acc, neg)
    prev_v, prev_i = vals_ref[...], gid_ref[...]
    ext = jnp.concatenate([acc, prev_v], axis=0)
    _fold_winners(ext, lambda am: blk * block_size + am, prev_i,
                  mv_ref, mi_ref, n_rows=block_size, k=k)
    vals_ref[...] = mv_ref[...]
    gid_ref[...] = mi_ref[...]


def _resident_kernel(desc_ref, w_ref, doc_hbm, sc_hbm, vals_ref, gid_ref,
                     acc_ref, dbuf, sbuf, dsem, ssem, mv_ref, mi_ref, *,
                     block_size: int, frag: int, k: int, n_docs: int):
    """One fragment of the device-resident gather→score→top-k path.

    The grid walks the batch's fragment table (SMEM chunks; see
    ``sparse.block_csr.FragmentPlan`` for the row layout). Each step
    DMAs its ≤``frag`` postings (doc ids + eager scores) out of the
    HBM-resident CSC arrays at a descriptor-driven dynamic offset, scales
    by the owning token's ``[B]`` query-weight row (named by the
    descriptor — no membership search), and one-hot-scatters into the
    current document block's ``[block_size, B]`` accumulator. Block-final
    fragments mask tail-padding docs and fold the block into the running
    shard ``[k, B]`` scoreboard (two-level reduce).

    This SINGLE-BUFFER variant issues its two DMAs sequentially and waits
    before scoring — the exactness oracle for the double-buffered pipeline
    (:func:`_resident_kernel_db`), same role the two-step chunk merge
    plays for the two-level reduce.
    """
    i = pl.program_id(0)
    start, valid, uidx, blk, first, last = _fragment(desc_ref, i)
    align, width = _dma_window(frag)
    base, off = _window_start(start, align)
    neg = jnp.finfo(vals_ref.dtype).min

    @pl.when(i == 0)
    def _init_out():
        vals_ref[...] = jnp.full_like(vals_ref, neg)
        gid_ref[...] = jnp.full_like(gid_ref, -1)

    @pl.when(first == 1)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(valid > 0)
    def _score():
        cp_d = pltpu.make_async_copy(
            doc_hbm.at[pl.ds(0, 1), pl.ds(base, width)], dbuf, dsem)
        cp_s = pltpu.make_async_copy(
            sc_hbm.at[pl.ds(0, 1), pl.ds(base, width)], sbuf, ssem)
        cp_d.start()
        cp_s.start()
        cp_d.wait()
        cp_s.wait()
        _resident_scatter(acc_ref, w_ref, dbuf[0, :], sbuf[0, :], off,
                          valid, uidx, blk, block_size=block_size,
                          width=width)

    @pl.when(last == 1)
    def _reduce():
        _resident_fold(acc_ref, vals_ref, gid_ref, mv_ref, mi_ref, blk,
                       block_size=block_size, k=k, n_docs=n_docs)


def _resident_kernel_db(desc_ref, nxt_ref, w_ref, doc_hbm, sc_hbm, vals_ref,
                        gid_ref, acc_ref, dbuf0, sbuf0, dbuf1, sbuf1, dsem0,
                        ssem0, dsem1, ssem1, mv_ref, mi_ref, *,
                        block_size: int, frag: int, k: int, n_docs: int):
    """Double-buffered variant: fragment f+1's DMAs fly during f's scatter.

    Same math as :func:`_resident_kernel` (both call
    :func:`_resident_scatter`/:func:`_resident_fold`, so outputs are
    bit-identical); only the copy schedule changes. Two (doc, score)
    scratch slots alternate by fragment parity — the two-slot + two-
    semaphore pattern proven in ``kernels/embedding_bag.py``: grid step
    ``f`` starts fragment ``f+1``'s copies into the idle slot BEFORE
    waiting on its own, so on real hardware the HBM reads of the next
    fragment overlap the one-hot scatter matmul of the current one
    (interpret mode executes the copies eagerly — what the CPU tests
    validate). Every fragment is copied, padding included (``start`` is 0
    there and the resident arrays over-allocate a full ``frag`` tail), so
    start/wait stay balanced with no cross-step control flow; padding
    still contributes nothing because the scatter is gated on
    ``valid > 0``. ``nxt_ref`` is the fragment table's SMEM chunk that
    holds fragment ``i + 1``.
    """
    i = pl.program_id(0)
    nf = pl.num_programs(0)
    start, valid, uidx, blk, first, last = _fragment(desc_ref, i)
    align, width = _dma_window(frag)
    base, off = _window_start(start, align)
    even = i % 2 == 0
    neg = jnp.finfo(vals_ref.dtype).min

    def copies(s, dbuf, sbuf, dsem, ssem):
        return (pltpu.make_async_copy(
                    doc_hbm.at[pl.ds(0, 1), pl.ds(s, width)], dbuf, dsem),
                pltpu.make_async_copy(
                    sc_hbm.at[pl.ds(0, 1), pl.ds(s, width)], sbuf, ssem))

    @pl.when(i == 0)
    def _init_out():
        vals_ref[...] = jnp.full_like(vals_ref, neg)
        gid_ref[...] = jnp.full_like(gid_ref, -1)
        for cp in copies(base, dbuf0, sbuf0, dsem0, ssem0):
            cp.start()                            # warm-up: fragment 0

    @pl.when(first == 1)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # prefetch fragment i+1 into the slot this step does NOT consume
    @pl.when(i + 1 < nf)
    def _prefetch():
        nstart = _fragment(nxt_ref, i + 1)[0]
        nbase, _ = _window_start(nstart, align)

        @pl.when(even)
        def _into_slot1():
            for cp in copies(nbase, dbuf1, sbuf1, dsem1, ssem1):
                cp.start()

        @pl.when(jnp.logical_not(even))
        def _into_slot0():
            for cp in copies(nbase, dbuf0, sbuf0, dsem0, ssem0):
                cp.start()

    # wait on THIS fragment's slot (unconditionally — semaphores must
    # balance even for padding fragments)
    @pl.when(even)
    def _wait_slot0():
        for cp in copies(base, dbuf0, sbuf0, dsem0, ssem0):
            cp.wait()

    @pl.when(jnp.logical_not(even))
    def _wait_slot1():
        for cp in copies(base, dbuf1, sbuf1, dsem1, ssem1):
            cp.wait()

    @pl.when(valid > 0)
    def _score():
        doc = jnp.where(even, dbuf0[0, :], dbuf1[0, :])   # [W] int32
        sc = jnp.where(even, sbuf0[0, :], sbuf1[0, :])    # [W] f32
        _resident_scatter(acc_ref, w_ref, doc, sc, off, valid, uidx, blk,
                          block_size=block_size, width=width)

    @pl.when(last == 1)
    def _reduce():
        _resident_fold(acc_ref, vals_ref, gid_ref, mv_ref, mi_ref, blk,
                       block_size=block_size, k=k, n_docs=n_docs)


_BOUND_ROWS = 8      # sublane tile: bound rows reach the kernel 8 at a time


def _resident_kernel_pruned(desc_ref, w_ref, bnd_ref, doc_hbm, sc_hbm,
                            vals_ref, gid_ref, skip_ref, acc_ref, dbuf, sbuf,
                            dsem, ssem, mv_ref, mi_ref, *, block_size: int,
                            frag: int, k: int, n_docs: int):
    """Threshold-skipping variant: DMAs gated on the live scoreboard.

    Same scatter/fold math as :func:`_resident_kernel` (bit-identical by
    construction — both call :func:`_resident_scatter` /
    :func:`_resident_fold`), plus the block-max skip: each fragment's row
    of ``bnd_ref`` carries its document block's per-query score UPPER
    bound (``sparse.block_csr.block_upper_bounds``), and the running
    scoreboard's k-th value (row ``k-1`` — folds emit ranks in descending
    order) is a certified LOWER bound on every query's final k-th score.
    When no query's bound reaches its threshold, the fragment's postings
    cannot alter the scoreboard for ANY query, so both posting DMAs and
    the one-hot scatter are skipped — this is how a threshold that
    saturates mid-launch still cuts DMA traffic the pre-launch compaction
    could not see. Skipping is exact:

    * the board holds full scores of real documents only (a block's
      fragments are contiguous, so its accumulator is complete when it
      folds), so row ``k-1`` never overestimates the final k-th score;
    * the board is constant across one block's fragments (folds happen at
      block boundaries), so a block skips or scores ATOMICALLY — a
      partially-scored block cannot leak a too-low score into the fold
      (and a fully-skipped block's zero accumulator folds harmlessly: the
      skip condition forces board-min > bound ≥ 0);
    * bounds are slack-inflated upstream, so f32 accumulation rounding
      cannot push a real score past its bound.

    ``skip_ref`` counts skipped real fragments — the kernel-level half of
    the pruned regime's observability (``last_plan.frags_skipped``).
    ``bnd_ref`` holds the ``_BOUND_ROWS``-row group containing fragment
    ``i``'s bound row (a single-row block is not a legal TPU tile).
    """
    i = pl.program_id(0)
    start, valid, uidx, blk, first, last = _fragment(desc_ref, i)
    align, width = _dma_window(frag)
    base, off = _window_start(start, align)
    neg = jnp.finfo(vals_ref.dtype).min

    @pl.when(i == 0)
    def _init_out():
        vals_ref[...] = jnp.full_like(vals_ref, neg)
        gid_ref[...] = jnp.full_like(gid_ref, -1)
        skip_ref[...] = jnp.zeros_like(skip_ref)

    @pl.when(first == 1)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # live iff ANY query's threshold is still reachable by this block
    kth = vals_ref[pl.ds(k - 1, 1), :]                              # [1, B]
    bnd = bnd_ref[pl.ds(i % _BOUND_ROWS, 1), :]                     # [1, B]
    live = jnp.any(bnd >= kth)

    @pl.when((valid > 0) & live)
    def _score():
        cp_d = pltpu.make_async_copy(
            doc_hbm.at[pl.ds(0, 1), pl.ds(base, width)], dbuf, dsem)
        cp_s = pltpu.make_async_copy(
            sc_hbm.at[pl.ds(0, 1), pl.ds(base, width)], sbuf, ssem)
        cp_d.start()
        cp_s.start()
        cp_d.wait()
        cp_s.wait()
        _resident_scatter(acc_ref, w_ref, dbuf[0, :], sbuf[0, :], off,
                          valid, uidx, blk, block_size=block_size,
                          width=width)

    @pl.when((valid > 0) & jnp.logical_not(live))
    def _count_skip():
        skip_ref[...] += jnp.ones_like(skip_ref)

    @pl.when(last == 1)
    def _reduce():
        _resident_fold(acc_ref, vals_ref, gid_ref, mv_ref, mi_ref, blk,
                       block_size=block_size, k=k, n_docs=n_docs)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "frag", "k", "n_docs", "interpret"),
)
def bm25_resident_score_topk_pruned(desc: jax.Array, weights: jax.Array,
                                    bounds: jax.Array,
                                    doc_ids_res: jax.Array,
                                    scores_res: jax.Array, *,
                                    block_size: int, frag: int, k: int,
                                    n_docs: int,
                                    interpret: bool | None = None
                                    ) -> tuple[jax.Array, jax.Array,
                                               jax.Array]:
    """Pruned-regime resident scorer: skip fragments no posting can win.

    Identical contract to :func:`bm25_resident_score_topk` (single-buffer
    schedule) with one extra operand and output: ``bounds`` is the
    ``[nf_pad, B]`` float32 per-fragment block upper-bound table (row f =
    the batch's score upper bound for fragment f's document block, already
    slack-inflated), and the third output is the ``[1, 1]`` int32 count of
    real fragments whose DMAs the in-kernel threshold test skipped.
    Outputs (values, ids) are BIT-identical to the single-buffer kernel on
    the same descriptor table — the skip removes only provably-losing
    work (see :func:`_resident_kernel_pruned` for the argument).
    """
    nf = desc.shape[1]
    u, b = weights.shape
    _, width = _dma_window(frag)
    assert desc.shape[0] == 6, desc.shape
    assert bounds.shape == (nf, b), (bounds.shape, nf, b)
    assert nf % _BOUND_ROWS == 0, nf
    assert k <= block_size, (k, block_size)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    return pl.pallas_call(
        functools.partial(_resident_kernel_pruned, block_size=block_size,
                          frag=frag, k=k, n_docs=n_docs),
        grid=(nf,),
        in_specs=[
            _desc_spec(nf),                                  # desc / SMEM
            pl.BlockSpec((u, b), lambda i: (0, 0)),          # weights VMEM
            pl.BlockSpec((_BOUND_ROWS, b),
                         lambda i: (i // _BOUND_ROWS, 0)),   # bound rows
            pl.BlockSpec(memory_space=pl.ANY),               # doc ids / HBM
            pl.BlockSpec(memory_space=pl.ANY),               # scores / HBM
        ],
        out_specs=(
            pl.BlockSpec((k, b), lambda i: (0, 0)),          # shard values
            pl.BlockSpec((k, b), lambda i: (0, 0)),          # shard ids
            pl.BlockSpec((1, 1), lambda i: (0, 0)),          # skip count
        ),
        scratch_shapes=(
            [pltpu.VMEM((block_size, b), weights.dtype),     # block acc
             pltpu.VMEM((1, width), jnp.int32),              # doc-id window
             pltpu.VMEM((1, width), jnp.float32),            # score window
             pltpu.SemaphoreType.DMA,
             pltpu.SemaphoreType.DMA,
             pltpu.VMEM((k, b), weights.dtype),              # fold staging
             pltpu.VMEM((k, b), jnp.int32)]
        ),
        out_shape=(
            jax.ShapeDtypeStruct((k, b), weights.dtype),
            jax.ShapeDtypeStruct((k, b), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ),
        interpret=interpret,
        name="bm25_resident_score_topk_pruned",
    )(desc, weights, bounds, doc_ids_res, scores_res)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "frag", "k", "n_docs", "double_buffer",
                     "interpret"),
)
def bm25_resident_score_topk(desc: jax.Array, weights: jax.Array,
                             doc_ids_res: jax.Array, scores_res: jax.Array,
                             *, block_size: int, frag: int, k: int,
                             n_docs: int, double_buffer: bool = True,
                             interpret: bool | None = None
                             ) -> tuple[jax.Array, jax.Array]:
    """Fragment descriptors × resident index -> shard (values, ids) [k, B].

    ``desc`` is the ``[6, nf_pad]`` int32 table from
    ``sparse.block_csr.fragment_plan`` — or its device-built twin
    (``sparse.fragment_device.plan_fragments_device``), which never leaves
    HBM — read from SMEM in chunks so it can drive DMA descriptors;
    ``doc_ids_res``/``scores_res`` are the ``[1, nnz_pad]`` HBM-resident
    CSC arrays of a ``sparse.block_csr.DeviceIndex`` — the ONLY posting
    data the kernel touches, and it never crosses the host→device boundary
    per batch. Winners carry global doc ids; blocks the batch never visits
    are absent (their docs score raw 0 — the caller splices default
    documents, same contract as the host-gathered path).

    ``double_buffer=True`` (default) overlaps fragment ``f+1``'s posting
    DMAs with fragment ``f``'s scatter (two scratch slots, embedding_bag's
    pattern); ``False`` keeps the sequential-copy kernel — the exactness
    oracle the bit-identity tests compare against.
    """
    nf = desc.shape[1]
    u, b = weights.shape
    _, width = _dma_window(frag)
    assert desc.shape[0] == 6, desc.shape
    assert k <= block_size, (k, block_size)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    tile_scratch = [
        pltpu.VMEM((1, width), jnp.int32),               # doc-id window
        pltpu.VMEM((1, width), jnp.float32),             # score window
        pltpu.SemaphoreType.DMA,
        pltpu.SemaphoreType.DMA,
    ]
    if double_buffer:
        tile_scratch = [
            pltpu.VMEM((1, width), jnp.int32),           # slot-0 doc window
            pltpu.VMEM((1, width), jnp.float32),         # slot-0 scores
            pltpu.VMEM((1, width), jnp.int32),           # slot-1 doc window
            pltpu.VMEM((1, width), jnp.float32),         # slot-1 scores
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ]
    # the double-buffered kernel also reads fragment i+1's start
    descs = [desc, desc] if double_buffer else [desc]
    kernel = _resident_kernel_db if double_buffer else _resident_kernel
    return pl.pallas_call(
        functools.partial(kernel, block_size=block_size,
                          frag=frag, k=k, n_docs=n_docs),
        grid=(nf,),
        in_specs=[_desc_spec(nf, ahead) for ahead in range(len(descs))]
        + [
            pl.BlockSpec((u, b), lambda i: (0, 0)),          # weights VMEM
            pl.BlockSpec(memory_space=pl.ANY),               # doc ids / HBM
            pl.BlockSpec(memory_space=pl.ANY),               # scores / HBM
        ],
        out_specs=(
            pl.BlockSpec((k, b), lambda i: (0, 0)),          # shard values
            pl.BlockSpec((k, b), lambda i: (0, 0)),          # shard ids
        ),
        scratch_shapes=(
            [pltpu.VMEM((block_size, b), weights.dtype)]     # block acc
            + tile_scratch
            + [pltpu.VMEM((k, b), weights.dtype),            # fold staging
               pltpu.VMEM((k, b), jnp.int32)]
        ),
        out_shape=(
            jax.ShapeDtypeStruct((k, b), weights.dtype),
            jax.ShapeDtypeStruct((k, b), jnp.int32),
        ),
        interpret=interpret,
        name="bm25_resident_score_topk_db" if double_buffer
        else "bm25_resident_score_topk",
    )(*descs, weights, doc_ids_res, scores_res)
