"""The paper's own architecture: BM25S eager-sparse retrieval at pod scale.

Corpus: the paper's footnote-13 example — 2M documents, 200K vocabulary
(the dense score matrix would be 1.6 TB; eager-sparse is ~250M postings).
Queries arrive in batches of 256, ≤32 unique tokens each.

Two device cells (extra, beyond the 40 assigned cells):

  score_2m          — paper-faithful path: documents sharded over every mesh
                      axis, per-shard gather+segment_sum scoring (shard_map),
                      per-shard top-k, all-gather k·shards candidates, global
                      merge. Collective volume O(shards·k·8B).
  score_blocked_2m  — beyond-paper batched path (DESIGN.md §3.2/3.3): the
                      block-bucketed layout streamed once for the whole query
                      batch; scatter lowered as one-hot matmul on the MXU.
                      Lowered from the pure-jnp kernel oracle so the HLO is
                      shardable; the Pallas kernel is the TPU codegen of the
                      same contraction.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.variants import BM25Params
from .common import Cell, sds

N_DOCS = 2_097_152            # 2M docs (paper footnote 13 example)
N_VOCAB = 200_000
AVG_UNIQUE_TOKENS = 120       # postings per doc
QUERY_BATCH = 256
Q_MAX = 32
P_MAX = 16_384                # per-shard posting budget per query
TOP_K = 100
DOC_BLOCK = 512
U_MAX = 2048                  # unique tokens across the query batch

PARAMS = BM25Params(method="lucene", k1=1.5, b=0.75)

FAMILY = "bm25s"
CONFIG = dict(n_docs=N_DOCS, n_vocab=N_VOCAB, params=PARAMS)
SMOKE = dict(n_docs=512, n_vocab=256, params=PARAMS)


def _score_2m_cell() -> Cell:
    def build(mesh):
        from ..core.retrieval import make_sharded_retrieve
        axes = tuple(mesh.shape.keys())
        n_shards = int(np.prod(list(mesh.shape.values())))
        docs_per_shard = N_DOCS // n_shards
        nnz_per_shard = N_DOCS * AVG_UNIQUE_TOKENS // n_shards
        nnz_pad = int(-(-nnz_per_shard // 1024) * 1024)
        fn = make_sharded_retrieve(mesh, axes, p_max=P_MAX, k=TOP_K,
                                   n_docs_per_shard=docs_per_shard)
        idx_arrays = (
            sds((n_shards, N_VOCAB + 1), jnp.int32),   # indptr
            sds((n_shards, nnz_pad), jnp.int32),       # doc_ids
            sds((n_shards, nnz_pad), jnp.float32),     # scores
            sds((n_shards, N_VOCAB), jnp.float32),     # nonoccurrence
            sds((n_shards, 1), jnp.int32),             # offsets
            sds((n_shards, 1), jnp.int32),             # true doc counts
        )
        return fn, (idx_arrays,
                    sds((QUERY_BATCH, Q_MAX), jnp.int32),
                    sds((QUERY_BATCH, Q_MAX), jnp.float32))

    def shardings(mesh, args):
        idx_arrays, qt, qw = args
        axes = tuple(mesh.shape.keys())
        sh = tuple(NamedSharding(mesh, P(axes)) for _ in idx_arrays)
        return (sh, NamedSharding(mesh, P()), NamedSharding(mesh, P()))

    # useful work: gather+add of each query's postings on every shard
    flops = 2.0 * QUERY_BATCH * P_MAX * 1.0
    return Cell("bm25s", "score_2m", "retrieval", build, shardings, flops,
                note="paper-faithful gather+segment_sum (extra cell)")


def _score_blocked_cell(*, doc_block: int = DOC_BLOCK,
                        batch: int = QUERY_BATCH, u_max: int = U_MAX,
                        score_dtype=jnp.float32,
                        sharded_topk: bool = False,
                        note: str = "beyond-paper batched MXU path "
                                    "(extra cell)") -> Cell:
    n_blocks = N_DOCS // doc_block
    nnz_pad = int(-(-AVG_UNIQUE_TOKENS * doc_block // 512) * 512)

    def build(mesh):
        from jax import shard_map
        from ..kernels.ref import bm25_block_score_ref
        from ..core.retrieval import blockwise_topk
        axes = tuple(mesh.shape.keys())
        ax_sizes = [mesh.shape[a] for a in axes]
        n_shards = int(np.prod(ax_sizes))

        if sharded_topk:
            # GSPMD replicates the batched scatter-add output (it cannot
            # prove block-locality), gathering the full [C, B] scores to
            # every chip. shard_map makes the block-locality explicit:
            # per-shard scoring + per-shard top-k, merge only [S, B, K].
            per = n_blocks // n_shards
            docs_local = per * doc_block

            def local_fn(tok, loc, sc, uniq, weights):
                out = bm25_block_score_ref(tok, loc, sc, uniq, weights,
                                           block_size=doc_block)
                flat = jnp.transpose(out, (2, 0, 1)).reshape(
                    batch, docs_local)
                lv, li = jax.lax.top_k(flat, TOP_K)       # [B, K] local
                sid = jnp.zeros((), jnp.int32)
                for a in axes:
                    sid = sid * mesh.shape[a] + jax.lax.axis_index(a)
                gi = li + sid * docs_local
                return lv[None], gi[None]                 # keep shard dim

            smapped = shard_map(
                local_fn, mesh=mesh,
                in_specs=(P(axes, None), P(axes, None), P(axes, None),
                          P(), P()),
                out_specs=(P(axes, None, None), P(axes, None, None)))

            def fn(token_ids, local_doc, scores, uniq, weights):
                lv, gi = smapped(token_ids, local_doc, scores, uniq, weights)
                allv = jnp.transpose(lv, (1, 0, 2)).reshape(batch, -1)
                alli = jnp.transpose(gi, (1, 0, 2)).reshape(batch, -1)
                mv, mi = jax.lax.top_k(allv, TOP_K)
                return jnp.take_along_axis(alli, mi, axis=-1), mv
        else:
            def fn(token_ids, local_doc, scores, uniq, weights):
                out = bm25_block_score_ref(token_ids, local_doc, scores,
                                           uniq, weights,
                                           block_size=doc_block)
                flat = jnp.transpose(out, (2, 0, 1)).reshape(
                    batch, n_blocks * doc_block)
                idx, vals = blockwise_topk(flat, TOP_K, block=4096)
                return idx, vals

        return fn, (sds((n_blocks, nnz_pad), jnp.int32),
                    sds((n_blocks, nnz_pad), jnp.int32),
                    sds((n_blocks, nnz_pad), score_dtype),
                    sds((u_max,), jnp.int32),
                    sds((u_max, batch), score_dtype))

    def shardings(mesh, args):
        axes = tuple(mesh.shape.keys())
        blk = NamedSharding(mesh, P(axes, None))
        return (blk, blk, blk, NamedSharding(mesh, P()),
                NamedSharding(mesh, P()))

    # useful work: one multiply-add per (posting, query) with avg df hit rate
    flops = 2.0 * batch * N_DOCS * AVG_UNIQUE_TOKENS * (Q_MAX / N_VOCAB)
    return Cell("bm25s", "score_blocked_2m", "retrieval", build, shardings,
                flops, note=note)


def cells() -> list[Cell]:
    return [_score_2m_cell(), _score_blocked_cell()]
