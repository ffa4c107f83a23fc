"""The chip smoke's body, its refusal without a chip, and the compile cache.

``chip_smoke.py`` runs :func:`repro.launch.smoke.run_smoke` at the
deployment's size on a TPU. Here the same function runs at a tiny size on
the CPU (Pallas interpret mode) with the chip's serving defaults spelled
out — resident gather, device-side fragment planning — so every phase and
check of the script is exercised by tier-1, and a broken board fails it.
"""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.launch import smoke
from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR,
                                        enable_compile_cache)

# the smoke asserts zero degradations and zero steady-state bytes, which
# an armed chaos fault legitimately changes
pytestmark = pytest.mark.no_chaos

ROOT = Path(__file__).resolve().parents[1]

TINY = dict(n_docs=400, n_vocab=300, avg_len=20, n_queries=24, ks=(5, 20),
            forced_batch=4, engine_batch=8, variant_docs=150,
            retriever_opts=dict(gather="resident", plan="device",
                                block_size=16, tile=16, acc_block=16,
                                frag=8, q_max=8))


def test_smoke_passes_at_tiny_size_on_cpu(capsys):
    facts = smoke.run_smoke(smoke.SmokeConfig(**TINY))
    out = capsys.readouterr().out
    assert facts["platform"] == "cpu" and facts["nnz"] > 0
    assert "FAILED" not in out
    for label in ("frontend k=20 steady-state bytes", "pruned k=5",
                  "host-gather k=20", "engine k=5 degradations",
                  "robertson 150 docs pruned k=20",
                  "kernel resident (double-buffered) ran"):
        assert f"check {label}: ok" in out


def test_smoke_fails_on_a_wrong_board(monkeypatch):
    """A board that is off by more than the tolerance must fail the run."""
    real = smoke._board_errors

    def off_by_one(oracle, queries, ids, vals, k):
        return real(oracle, queries, ids, np.asarray(vals) + 1e-3, k)
    monkeypatch.setattr(smoke, "_board_errors", off_by_one)
    with pytest.raises(smoke.SmokeCheckError, match="frontend k=5"):
        smoke.run_smoke(smoke.SmokeConfig(**{**TINY, "ks": (5,)}))


def test_chip_smoke_refuses_without_a_tpu(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([]) == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "needs a TPU" in captured.err


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(ROOT / ".jax_cache") == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
