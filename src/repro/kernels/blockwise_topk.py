"""Pallas TPU kernel: per-block top-k by iterative max (select-and-mask).

The paper's top-k is average-O(n) selection (np.argpartition / XLA top_k).
The distributed generalization is lossless two-stage selection: every global
winner is a winner of its own block, so per-block top-k + a tiny global
merge equals a full sort's top-k. This kernel is the per-block stage; the
merge is ~``nb·k`` elements and runs as a plain ``lax.top_k`` (ops.py).

Each grid step owns one block and performs k rounds of
(max, argmax, mask-out) — k·O(block) work, all VPU-friendly 2D reductions.
For the k ≪ block regime this matches the paper's O(n) average contract.

``select_topk`` is the reusable reduction core: the fused score→top-k
kernel (``bm25_block_score.bm25_block_score_topk``) runs the same k rounds
column-wise over its VMEM accumulator, which is how the dense score matrix
never reaches HBM.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401


def select_topk(acc: jax.Array, k: int, *, axis: int,
                emit: Callable[[jax.Array, jax.Array, jax.Array], None]
                ) -> None:
    """k rounds of (max, argmax, mask-out) along ``axis`` of ``acc``.

    ``emit(i, vals, idxs)`` is called once per round with the round index
    and the selected values/indices (``acc``'s shape minus ``axis``); it is
    expected to store into output refs. VPU-only: reductions + a compare
    mask, no sorts.
    """
    neg = jnp.finfo(acc.dtype).min
    iota = jax.lax.broadcasted_iota(jnp.int32, acc.shape, axis)

    def body(i, cur):
        m = jnp.max(cur, axis=axis)
        am = jnp.argmax(cur, axis=axis).astype(jnp.int32)
        emit(i, m, am)
        return jnp.where(iota == jnp.expand_dims(am, axis), neg, cur)

    jax.lax.fori_loop(0, k, body, acc)


def _kernel(x_ref, vals_ref, idx_ref, *, k: int):
    def emit(i, m, am):
        vals_ref[pl.ds(0, 1), pl.ds(i, 1)] = m[:, None]
        idx_ref[pl.ds(0, 1), pl.ds(i, 1)] = am[:, None]

    select_topk(x_ref[...], k, axis=1, emit=emit)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def blockwise_topk_kernel(x: jax.Array, *, k: int,
                          interpret: bool | None = None
                          ) -> tuple[jax.Array, jax.Array]:
    """[nb, block] -> (values [nb, k], local indices [nb, k]), descending."""
    nb, blk = x.shape
    assert k <= blk, (k, blk)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    vals, idx = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, blk), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((1, k), lambda i: (i, 0)),
            pl.BlockSpec((1, k), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((nb, k), x.dtype),
            jax.ShapeDtypeStruct((nb, k), jnp.int32),
        ),
        interpret=interpret,
        name="blockwise_topk",
    )(x)
    return vals, idx
