"""Pallas TPU kernel: EmbeddingBag (DMA row gather + weighted reduce).

JAX has no ``nn.EmbeddingBag``; the jnp formulation (``sparse/embedding_bag``)
materializes a ``[B, F, D]`` gather before reducing. This kernel is the
TPU-native version: the table stays in HBM (``memory_space=ANY``), bag
indices are scalar-prefetched into SMEM so they can drive DMA descriptors,
and each bag's rows are streamed row-by-row into a VMEM scratch buffer and
accumulated in registers — the ``[B, F, D]`` intermediate never exists.

On real hardware the row DMAs of consecutive fanout slots overlap with the
accumulate of the previous row (double-buffered scratch); in interpret mode
the copies execute eagerly, which is what the CPU tests validate.

Grid: ``(B // tile_b,)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, w_ref, table_ref, out_ref, row0, row1, sem0, sem1, *,
            fanout: int, tile_b: int):
    pi = pl.program_id(0)

    def bag_body(bi, _):
        gb = pi * tile_b + bi                     # global bag id (SMEM index)

        def start_dma(f, slot_ref, sem):
            idx = idx_ref[gb, f]
            safe = jnp.maximum(idx, 0)
            return pltpu.make_async_copy(
                table_ref.at[pl.ds(safe, 1), :], slot_ref, sem)

        # double-buffered fanout loop: issue f+1's DMA before reducing f
        start_dma(0, row0, sem0).start()

        def fan_body(f, acc):
            cur_row, cur_sem = jax.lax.cond(
                f % 2 == 0, lambda: (0, 0), lambda: (1, 1))
            # issue the next row's copy into the other buffer
            @pl.when(f + 1 < fanout)
            def _prefetch():
                nxt = f + 1

                @pl.when(nxt % 2 == 0)
                def _():
                    start_dma(nxt, row0, sem0).start()

                @pl.when(nxt % 2 == 1)
                def _():
                    start_dma(nxt, row1, sem1).start()

            @pl.when(cur_row == 0)
            def _():
                pltpu.make_async_copy(table_ref, row0, sem0).wait()

            @pl.when(cur_row == 1)
            def _():
                pltpu.make_async_copy(table_ref, row1, sem1).wait()

            row = jnp.where(cur_row == 0, row0[0, :], row1[0, :])
            idx = idx_ref[gb, f]
            w = jnp.where(idx >= 0, w_ref[bi, f], 0.0)
            return acc + w * row

        acc = jax.lax.fori_loop(
            0, fanout, fan_body,
            jnp.zeros((out_ref.shape[1],), out_ref.dtype))
        out_ref[pl.ds(bi, 1), :] = acc[None, :]
        return 0

    jax.lax.fori_loop(0, tile_b, bag_body, 0)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def embedding_bag_kernel(table: jax.Array, indices: jax.Array,
                         weights: jax.Array, *, tile_b: int = 128,
                         interpret: bool | None = None) -> jax.Array:
    """[V, D] table + [B, F] indices (-1 pad) + [B, F] weights -> [B, D]."""
    v, d = table.shape
    b, f = indices.shape
    assert weights.shape == (b, f)
    assert b % tile_b == 0, (b, tile_b)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                    # indices -> SMEM
        grid=(b // tile_b,),
        in_specs=[
            pl.BlockSpec((tile_b, f), lambda i, idx: (i, 0)),     # weights
            pl.BlockSpec(memory_space=pl.ANY),                    # table/HBM
        ],
        out_specs=pl.BlockSpec((tile_b, d), lambda i, idx: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, d), table.dtype),
            pltpu.VMEM((1, d), table.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, fanout=f, tile_b=tile_b),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        interpret=interpret,
        name="embedding_bag",
    )(indices, weights, table)
