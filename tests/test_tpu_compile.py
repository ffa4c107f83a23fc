"""Compile rehearsals: every serving kernel compiles for a TPU v5e.

Interpret mode (every other test) cannot see what the chip's compiler
refuses — unaligned DMA slices, illegal block shapes, gathers Mosaic does
not lower. These tests compile each kernel of the served path for a
described (not attached) v5e chip at the ``configs/bm25s.py`` deployment's
shapes: 2M documents (~252M postings), block 512, fragment 512, the
frontend's 32-query batches with a 256-token table, k=10 and k=100. Nothing
runs; a refusal surfaces as a compile error here instead of on the chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and collection must not depend on it.
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.bm25s import (AVG_UNIQUE_TOKENS, DOC_BLOCK, N_DOCS,
                                 N_VOCAB, TOP_K)
from repro.kernels.bm25_block_score import bm25_block_score_topk
from repro.kernels.bm25_gather_score import (bm25_gather_score_topk,
                                             bm25_resident_score_topk,
                                             bm25_resident_score_topk_pruned)
from repro.sparse.fragment_device import (block_bounds_device,
                                          build_fragment_table)

B, U, FRAG, NF = 32, 256, 512, 1 << 17
NNZ = N_DOCS * AVG_UNIQUE_TOKENS
NNZ_PAD = -(-NNZ // FRAG) * FRAG + FRAG
NB = N_DOCS // DOC_BLOCK
P_PAD = AVG_UNIQUE_TOKENS * DOC_BLOCK            # posting row per doc block
KS = (10, TOP_K)


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip (a ``SingleDeviceSharding``)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(chip, fn, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return jax.jit(functools.partial(fn, **static)).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


I32, F32 = jnp.int32, jnp.float32
RESIDENT = dict(block_size=DOC_BLOCK, frag=FRAG, n_docs=N_DOCS,
                interpret=False)


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("double_buffer", [True, False])
def test_resident_kernel_compiles(chip, k, double_buffer):
    _assert_kernel(_compile(
        chip, bm25_resident_score_topk, ((6, NF), I32), ((U, B), F32),
        ((1, NNZ_PAD), I32), ((1, NNZ_PAD), F32), k=k,
        double_buffer=double_buffer, **RESIDENT))


@pytest.mark.parametrize("k", KS)
def test_pruned_kernel_compiles(chip, k):
    _assert_kernel(_compile(
        chip, bm25_resident_score_topk_pruned, ((6, NF), I32),
        ((U, B), F32), ((NF, B), F32), ((1, NNZ_PAD), I32),
        ((1, NNZ_PAD), F32), k=k, **RESIDENT))


@pytest.mark.parametrize("k", KS)
def test_blocked_kernel_compiles(chip, k):
    rows = (NB, 1, P_PAD)
    _assert_kernel(_compile(
        chip, bm25_block_score_topk, (rows, I32), (rows, I32), (rows, F32),
        ((U,), I32), ((U, B), F32), block_size=DOC_BLOCK, k=k,
        n_docs=N_DOCS, tile_p=512, interpret=False))


@pytest.mark.parametrize("k", KS)
def test_host_gather_kernel_compiles(chip, k):
    nc, p = 512, 4096
    _assert_kernel(_compile(
        chip, bm25_gather_score_topk, ((nc, p), I32), ((nc, p), I32),
        ((nc, p), F32), ((U,), I32), ((U, B), F32), ((nc, 512), I32),
        acc_block=512, k=k, tile_p=512, two_level=True, interpret=False))


def test_device_fragment_builder_compiles(chip):
    # a 16M-posting batch: its scratch must leave the chip to the index
    compiled = _compile(
        chip, build_fragment_table, ((U,), I32), ((N_VOCAB + 1,), I32),
        ((1, NNZ_PAD), I32), block_size=DOC_BLOCK, frag=FRAG,
        nf_pad=1 << 16, p_bucket=1 << 24, k=TOP_K, n_docs=N_DOCS)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_device_block_bounds_compile(chip):
    nb_pad = 1 << int(np.ceil(np.log2(NB)))
    _compile(chip, block_bounds_device, ((N_VOCAB, nb_pad), jnp.uint8),
             ((N_VOCAB,), F32), ((U,), I32), ((U, B), F32), quantized=True)
