"""Pallas TPU kernel: batched BM25S scoring over block-bucketed postings.

This is the paper's hot loop ("slice query-token rows, sum over the token
dimension") re-architected for the TPU memory hierarchy (DESIGN.md §3):

* postings live in the static block-bucketed layout (block_csr.py) so every
  tile is a dense VMEM-resident rectangle;
* the per-posting "is this token in the query batch, at what weight?" lookup
  is a ``[P, U]`` equality one-hot against the unique-token table (O(P·U)
  VPU compares) times the ``[U, B]`` weight table on the MXU — Mosaic has
  no in-kernel row gather, and at most one entry per one-hot row is set;
* the scatter ``acc[local_doc] += score·w`` is a second one-hot matmul
  (``one_hot(local_doc)ᵀ @ contrib``) — the classic TPU answer to random
  scatter, with the one-hot built in-register from ``broadcasted_iota``.

Both one-hot matmuls run at ``Precision.HIGHEST`` (:func:`onehot_dot`): a
one-hot entry is exactly 1.0, so at full f32 precision every product is
exact and the kernel reproduces the oracle's per-posting contributions —
the MXU's default bf16 passes would round each weight to 8 mantissa bits.

Posting arrays enter as ``[n, 1, P]`` rows (2-D ``[n, P]`` inputs are
reshaped): a ``(1, tile_p)`` block of a 2-D array is not a legal TPU tile,
while the squeezed leading dimension of the 3-D form is. The resident
layout is stored 3-D already, so the reshape is free on the hot path.

Grid: ``(n_blocks, nnz_pad // tile_p)``. The inner (posting-tile) dimension
revisits the same output block, accumulating; program 0 zero-initializes.
Arithmetic intensity grows with the query batch B, which is what turns the
paper's memory-bound slice-and-sum into a compute-bound GEMM (§Perf).

Two entry points share the scoring tile:

* ``bm25_block_score``       — dense ``[nb, block_size, B]`` scores. Oracle /
  debug path only; at realistic corpus sizes this round-trips the whole
  score matrix through HBM.
* ``bm25_block_score_topk``  — the FUSED retrieval path. The accumulator
  lives in VMEM scratch; the last posting tile of each doc-block reduces it
  to per-block top-k (``select_topk`` rounds of max/argmax/mask, the
  ``blockwise_topk`` reduction run column-wise) and only ``[nb, k, B]``
  ids+values ever reach HBM — ``block_size/k`` less traffic, and no second
  kernel launch to re-read the scores.

Retrieval regimes — this file is the FULL-SCAN one. Its grid walks every
posting tile in the shard per query batch: O(nnz) compares/scatters
regardless of the query, which buys perfect streaming locality and zero
per-query layout work. That trade only wins when the batch is dense enough
that Σ df(q) approaches nnz (every tile would be gathered anyway — e.g.
huge batches of head-token queries, or vocabularies so small every token
matches most docs). For everything else the QUERY-GATHERED regime
(``bm25_gather_score.py``) does O(Σ df(q)) work — it slices only the query
tokens' posting runs and scatters into a candidate-sized accumulator — and
its advantage over the full scan grows linearly with corpus size at fixed
query df. ``serve.retrieval_engine``'s ``DeviceRetriever`` keeps BOTH
layouts HBM-resident and picks per batch via the free nnz/Σdf cost model
(``core.retrieval.plan_retrieval``, ``scorer="auto"``; ``"blocked"`` /
``"gathered"`` force a regime).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .blockwise_topk import select_topk


def onehot_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at full f32 precision (exact when ``a`` is a 0/1 one-hot)."""
    return jax.lax.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def as_posting_rows(*arrays: jax.Array) -> tuple[jax.Array, ...]:
    """``[n, P]`` -> ``[n, 1, P]`` (3-D inputs pass through unchanged)."""
    return tuple(a if a.ndim == 3 else a[:, None, :] for a in arrays)


def posting_row_spec(tile_p: int) -> pl.BlockSpec:
    """One ``(1, tile_p)`` posting row of an ``[n, 1, P]`` array per step."""
    return pl.BlockSpec((None, 1, tile_p), lambda i, j: (i, 0, j))


def _score_tile(tok_ref, loc_ref, sc_ref, uniq_ref, w_ref, *,
                block_size: int) -> jax.Array:
    """One posting tile's ``[block_size, B]`` score contribution."""
    tok = tok_ref[0, :]                                   # [PT] int32
    loc = loc_ref[0, :]                                   # [PT] int32
    sc = sc_ref[0, :]                                     # [PT] f32
    uniq = uniq_ref[...]                                  # [1, U] int32
    weights = w_ref[...]                                  # [U, B] f32

    # membership one-hot: uniq holds distinct tokens padded with INT32_MAX
    # and posting pads carry tok = -1, so each row has at most one hit and
    # the matmul returns that token's weight row (or zeros) exactly
    member = (uniq == tok[:, None]).astype(weights.dtype)       # [PT, U]
    contrib = onehot_dot(member, weights) * sc[:, None]         # [PT, B]

    # scatter -> one-hot matmul: oneh[d, p] = (loc[p] == d)
    d_iota = jax.lax.broadcasted_iota(jnp.int32, (block_size, loc.shape[0]), 0)
    oneh = (d_iota == loc[None, :]).astype(weights.dtype)        # [BS, PT]
    return onehot_dot(oneh, contrib)                             # [BS, B]


def _kernel(tok_ref, loc_ref, sc_ref, uniq_ref, w_ref, out_ref, *,
            block_size: int):
    """Dense variant: one (doc-block, posting-tile) grid step."""
    pj = pl.program_id(1)

    @pl.when(pj == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0, :, :] += _score_tile(tok_ref, loc_ref, sc_ref, uniq_ref,
                                    w_ref, block_size=block_size)


def _fused_kernel(tok_ref, loc_ref, sc_ref, uniq_ref, w_ref,
                  vals_ref, idx_ref, acc_ref, *,
                  block_size: int, k: int, n_docs: int):
    """Fused variant: accumulate in VMEM scratch, emit only top-k.

    The ``[block_size, B]`` accumulator never leaves VMEM; the final posting
    tile of each doc-block masks the tail-padding documents and runs k
    select-and-mask rounds column-wise (one winner per query per round).
    """
    # program ids are read at the top level: pl.program_id may not appear
    # inside a pl.when branch (interpret-mode lowering rejects it there).
    pi = pl.program_id(0)
    pj = pl.program_id(1)

    @pl.when(pj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _score_tile(tok_ref, loc_ref, sc_ref, uniq_ref, w_ref,
                                block_size=block_size)

    @pl.when(pj == pl.num_programs(1) - 1)
    def _reduce():
        acc = acc_ref[...]                                       # [BS, B]
        # docs past n_docs exist only as block padding; a padded doc's
        # accumulator is 0.0 which would outrank real negative scores
        # (robertson IDF can go negative), so mask before selecting.
        row = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        gdoc = pi * block_size + row
        acc = jnp.where(gdoc < n_docs, acc, jnp.finfo(acc.dtype).min)

        def emit(i, m, am):                                      # m, am: [B]
            b = m.shape[0]
            vals_ref[pl.ds(0, 1), pl.ds(i, 1), pl.ds(0, b)] = m[None, None, :]
            idx_ref[pl.ds(0, 1), pl.ds(i, 1), pl.ds(0, b)] = am[None, None, :]

        select_topk(acc, k, axis=0, emit=emit)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "tile_p", "interpret"),
)
def bm25_block_score(token_ids: jax.Array, local_doc: jax.Array,
                     scores: jax.Array, uniq_tokens: jax.Array,
                     weights: jax.Array, *, block_size: int,
                     tile_p: int = 512, interpret: bool | None = None
                     ) -> jax.Array:
    """[nb, (1,) P] blocked postings x [U, B] query table -> [nb, block_size, B].

    Dense scores for oracle tests and full-score consumers; the retrieval
    path uses :func:`bm25_block_score_topk` instead.
    """
    token_ids, local_doc, scores = as_posting_rows(token_ids, local_doc,
                                                   scores)
    nb, _, p = token_ids.shape
    u, b = weights.shape
    assert p % tile_p == 0, (p, tile_p)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    grid = (nb, p // tile_p)
    return pl.pallas_call(
        functools.partial(_kernel, block_size=block_size),
        grid=grid,
        in_specs=[
            posting_row_spec(tile_p),                             # token_ids
            posting_row_spec(tile_p),                             # local_doc
            posting_row_spec(tile_p),                             # scores
            pl.BlockSpec((1, u), lambda i, j: (0, 0)),            # uniq table
            pl.BlockSpec((u, b), lambda i, j: (0, 0)),            # weights
        ],
        out_specs=pl.BlockSpec((1, block_size, b), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block_size, b), weights.dtype),
        interpret=interpret,
        name="bm25_block_score",
    )(token_ids, local_doc, scores, uniq_tokens.reshape(1, u), weights)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "k", "n_docs", "tile_p", "interpret"),
)
def bm25_block_score_topk(token_ids: jax.Array, local_doc: jax.Array,
                          scores: jax.Array, uniq_tokens: jax.Array,
                          weights: jax.Array, *, block_size: int, k: int,
                          n_docs: int, tile_p: int = 512,
                          interpret: bool | None = None
                          ) -> tuple[jax.Array, jax.Array]:
    """Fused score→top-k: blocked postings -> (values, local ids) [nb, k, B].

    HBM sees only the ``[nb, k, B]`` winners — the dense
    ``[nb, block_size, B]`` matrix stays in a VMEM scratch accumulator.
    Padded documents (global id ≥ ``n_docs``) are masked to -inf before
    selection, so they can only surface when a block holds fewer than ``k``
    real documents. Ids are block-local; the merge adds ``block·block_size``.
    """
    token_ids, local_doc, scores = as_posting_rows(token_ids, local_doc,
                                                   scores)
    nb, _, p = token_ids.shape
    u, b = weights.shape
    assert p % tile_p == 0, (p, tile_p)
    assert k <= block_size, (k, block_size)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    grid = (nb, p // tile_p)
    return pl.pallas_call(
        functools.partial(_fused_kernel, block_size=block_size, k=k,
                          n_docs=n_docs),
        grid=grid,
        in_specs=[
            posting_row_spec(tile_p),                             # token_ids
            posting_row_spec(tile_p),                             # local_doc
            posting_row_spec(tile_p),                             # scores
            pl.BlockSpec((1, u), lambda i, j: (0, 0)),            # uniq table
            pl.BlockSpec((u, b), lambda i, j: (0, 0)),            # weights
        ],
        out_specs=(
            pl.BlockSpec((1, k, b), lambda i, j: (i, 0, 0)),      # values
            pl.BlockSpec((1, k, b), lambda i, j: (i, 0, 0)),      # local ids
        ),
        out_shape=(
            jax.ShapeDtypeStruct((nb, k, b), weights.dtype),
            jax.ShapeDtypeStruct((nb, k, b), jnp.int32),
        ),
        scratch_shapes=[pltpu.VMEM((block_size, b), weights.dtype)],
        interpret=interpret,
        name="bm25_block_score_topk",
    )(token_ids, local_doc, scores, uniq_tokens.reshape(1, u), weights)
