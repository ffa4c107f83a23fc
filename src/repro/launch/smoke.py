"""End-to-end smoke of the served retrieval path, exact against the oracle.

:func:`run_smoke` builds the ``configs/bm25s.py`` deployment from a seed
(2,097,152 Zipf documents over a 200k-term vocabulary, about 120 postings
per document, lucene), serves it through the entry points a user calls and
compares every returned ``[k]`` board with ``ScipyBM25``:

1. single queries through :class:`~repro.serve.ServingFrontend` over the
   default :class:`~repro.serve.DeviceRetriever`, at k=10 and at the
   config's k=100, after a warm pass — asserting zero degradations,
   device-side fragment planning and zero posting/descriptor bytes
   shipped host→device while serving;
2. one strict batch forced into each regime (blocked full scan, resident
   gather, block-max pruned) plus the host-gather rung;
3. the engine path (``RetrievalEngine(scorer="auto", quorum=1.0)``);
4. all five BM25 variants at a reduced document count, every regime;
5. on a TPU, that every Pallas kernel those calls ran was compiled for the
   chip (``tpu_custom_call`` in its compiled text), not interpreted.

Each check prints one line and a failed check raises
:class:`SmokeCheckError`. The set-up lines (device, sizes, build and
compile seconds, peak device memory) are facts about the run, not metrics.
``chip_smoke.py`` at the repository root runs this at full size on a TPU;
the tier-1 test runs it at a tiny size on the CPU (interpret mode).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from ..configs.bm25s import N_DOCS, N_VOCAB, PARAMS, TOP_K

VARIANTS = ("robertson", "atire", "lucene", "bm25l", "bm25+")
FORCED_REGIMES = ("blocked", "gathered", "pruned")
# tokens per document: zipf_corpus at a 200k vocabulary then yields about
# 120 distinct tokens (postings) per document, the deployment's density
AVG_DOC_LEN = 175


class SmokeCheckError(AssertionError):
    """A smoke check failed (wrong board, degradation, bytes shipped...)."""


@dataclass
class SmokeConfig:
    n_docs: int = N_DOCS
    n_vocab: int = N_VOCAB
    avg_len: int = AVG_DOC_LEN
    n_queries: int = 128
    ks: tuple[int, ...] = (10, TOP_K)
    forced_batch: int = 8            # queries per forced-regime batch
    engine_batch: int = 32
    variant_docs: int = 1 << 14      # reduced size of the five-variant pass
    seed: int = 0
    # DeviceRetriever keywords; empty = the defaults a deployment gets
    retriever_opts: dict = field(default_factory=dict)


class _CompileLog:
    """Counts backend compilations and their seconds (monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


class _KernelCalls:
    """Records the first call of each kernel entry point the path runs, so
    its compiled text can be inspected afterwards (same arguments, so the
    lowering hits the executable the path ran)."""

    def __init__(self):
        from ..kernels import bm25_gather_score, ops
        from ..sparse import fragment_device
        self.targets = {
            "resident (double-buffered)": (ops, "bm25_retrieve_resident"),
            "resident (single-buffered)": (bm25_gather_score,
                                           "bm25_resident_score_topk"),
            "pruned": (ops, "_bm25_retrieve_resident_pruned_jit"),
            "blocked": (ops, "bm25_retrieve_blocked"),
            "host gather (two-level)": (ops, "bm25_retrieve_gathered"),
            "device fragment builder": (fragment_device,
                                        "build_fragment_table"),
        }
        self.calls: dict[str, tuple] = {}
        self._saved: list[tuple] = []

    def __enter__(self):
        for label, (mod, name) in self.targets.items():
            fn = getattr(mod, name)
            self._saved.append((mod, name, fn))

            def rec(*a, _fn=fn, _label=label, **kw):
                self.calls.setdefault(_label, (_fn, a, kw))
                return _fn(*a, **kw)
            setattr(mod, name, rec)
        return self

    def __exit__(self, *exc):
        for mod, name, fn in self._saved:
            setattr(mod, name, fn)


def _check(ok: bool, name: str, detail: str = "") -> None:
    print(f"check {name}: {'ok' if ok else 'FAILED'}"
          f"{' (' + detail + ')' if detail else ''}", flush=True)
    if not ok:
        raise SmokeCheckError(f"{name}: {detail}")


def _board_errors(oracle, queries, ids, vals, k) -> list[str]:
    """Tie-aware exactness, as the tests compare: the board's values equal
    the oracle's top-k values and every returned id carries its exact
    oracle score (atol 1e-4)."""
    from ..core.retrieval import topk_numpy
    errs = []
    for i, q in enumerate(queries):
        s = oracle.score(np.asarray(q))
        _, ref = topk_numpy(s[None], k)
        got_i, got_v = np.asarray(ids[i]), np.asarray(vals[i])
        if got_v.shape != ref[0].shape:
            errs.append(f"query {i}: board {got_v.shape} vs {ref[0].shape}")
        elif np.unique(got_i).size != got_i.size:
            errs.append(f"query {i}: duplicate ids")
        elif not (np.allclose(got_v, ref[0], rtol=0, atol=1e-4)
                  and np.allclose(s[got_i], got_v, rtol=0, atol=1e-4)):
            errs.append(f"query {i}: max value error "
                        f"{np.abs(got_v - ref[0]).max():.3g}")
    return errs


def _check_boards(name, oracle, queries, ids, vals, k) -> None:
    errs = _board_errors(oracle, queries, ids, vals, k)
    _check(not errs, name, f"{len(queries)} boards of k={k} exact vs "
                           f"ScipyBM25" if not errs else "; ".join(errs[:3]))


def _serve_frontend(dr, queries, k):
    from ..serve import ServingFrontend
    with ServingFrontend(dr, k=k) as fe:
        futs = [fe.submit(q) for q in queries]
        res = [f.result(timeout=600) for f in futs]
        health = fe.health()
    return (np.stack([r.ids for r in res]), np.stack([r.scores for r in res]),
            health)


def _serve_pass(cfg, dr, oracle, queries):
    """Phase 1: single queries through the frontend at every k."""
    from ..sparse.block_csr import TRANSFERS, reset_transfer_stats
    for k in cfg.ks:
        dr.warmup(k=k)
        _serve_frontend(dr, queries, k)            # warm pass: compiles
        reset_transfer_stats()
        ids, vals, health = _serve_frontend(dr, queries, k)
        moved = (TRANSFERS.posting_bytes, TRANSFERS.descriptor_bytes)
        _check_boards(f"frontend k={k}", oracle, queries, ids, vals, k)
        _check(health["degraded"] == 0 and dr.health()["degraded"] == 0,
               f"frontend k={k} degradations",
               f"{health['served']} requests in {health['batches']} "
               f"batches, {health['degraded']} degraded")
        _check(dr.last_plan.plan == "device", f"frontend k={k} plan",
               f"fragment tables built on {dr.last_plan.plan}")
        _check(moved == (0, 0), f"frontend k={k} steady-state bytes",
               f"posting {moved[0]} B, descriptor {moved[1]} B")


def _forced_pass(cfg, index, dr, oracle, queries, label=""):
    """Phase 2: one strict batch per regime, plus the host-gather rung."""
    from ..serve import DeviceRetriever
    host = DeviceRetriever(index, regime="gathered",
                           **{**cfg.retriever_opts, "gather": "host",
                              "plan": "host"})
    for k in cfg.ks:
        for regime in FORCED_REGIMES:
            r = dr.retrieve_batch(queries, k, regime=regime)
            _check(r.plan.regime == regime and not r.degraded,
                   f"{label}{regime} k={k} regime",
                   f"ran {r.plan.regime}, sum_df {r.plan.sum_df}")
            _check_boards(f"{label}{regime} k={k}", oracle, queries,
                          r.ids, r.scores, k)
        r = host.retrieve_batch(queries, k, regime="gathered")
        _check_boards(f"{label}host-gather k={k}", oracle, queries, r.ids,
                      r.scores, k)


def _engine_pass(cfg, index, dr, oracle, queries):
    """Phase 3: the sharded engine path, adopting the resident index."""
    from ..serve import RetrievalEngine
    for k in cfg.ks:
        eng = RetrievalEngine([index], k=k, scorer="auto", quorum=1.0,
                              device_indexes=[dr.dindex],
                              scorer_opts=cfg.retriever_opts)
        r = eng.retrieve_batch(queries, k=k)
        _check_boards(f"engine k={k}", oracle, queries, r.ids, r.scores, k)
        h = eng.health()
        _check(r.shards_answered == 1 and h["degraded"] == 0
               and all(s["degraded"] == 0 for s in h["shards"]),
               f"engine k={k} degradations",
               f"{r.shards_answered} of 1 shards answered")


def _variant_pass(cfg, queries):
    """Phase 4: the five variants at a reduced size, every regime."""
    from ..core import BM25Params, ScipyBM25, build_index
    from ..data.corpus import zipf_corpus
    from ..serve import DeviceRetriever
    corpus = zipf_corpus(cfg.variant_docs, cfg.n_vocab, avg_len=cfg.avg_len,
                         seed=cfg.seed + 2)
    for method in VARIANTS:
        idx = build_index(corpus, cfg.n_vocab, params=BM25Params(
            method=method, k1=PARAMS.k1, b=PARAMS.b))
        dr = DeviceRetriever(idx, **cfg.retriever_opts)
        _forced_pass(cfg, idx, dr, ScipyBM25(idx), queries,
                     label=f"{method} {cfg.variant_docs} docs ")


def run_smoke(cfg: SmokeConfig | None = None) -> dict:
    """Run every phase; raises :class:`SmokeCheckError` on a failed check.

    Returns the set-up facts it printed (device, sizes, seconds).
    """
    import jax
    import jax.monitoring

    from ..core import ScipyBM25, build_index
    from ..data.corpus import zipf_corpus, zipf_queries
    from ..serve import DeviceRetriever

    cfg = cfg or SmokeConfig()
    dev = jax.devices()[0]
    compiles = _CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    facts = {"platform": dev.platform, "device_kind": dev.device_kind}
    print(f"setup device {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}", flush=True)
    try:
        t = time.perf_counter()
        corpus = zipf_corpus(cfg.n_docs, cfg.n_vocab, avg_len=cfg.avg_len,
                             seed=cfg.seed)
        index = build_index(corpus, cfg.n_vocab, params=PARAMS)
        del corpus
        facts["host_build_s"] = time.perf_counter() - t
        t = time.perf_counter()
        dr = DeviceRetriever(index, **cfg.retriever_opts)
        facts["device_index_build_s"] = time.perf_counter() - t
        oracle = ScipyBM25(index)
        facts.update(n_docs=cfg.n_docs, n_vocab=cfg.n_vocab, nnz=index.nnz)
        print(f"setup n_docs {cfg.n_docs} n_vocab {cfg.n_vocab} "
              f"nnz {index.nnz} ({index.nnz / cfg.n_docs:.1f} per doc, "
              f"{PARAMS.method})", flush=True)
        print(f"setup host index build {facts['host_build_s']:.1f} s, "
              f"device index build+upload "
              f"{facts['device_index_build_s']:.1f} s", flush=True)
        queries = zipf_queries(cfg.n_queries, cfg.n_vocab, q_len=5,
                               seed=cfg.seed + 1)
        phases = (
            ("frontend", lambda: _serve_pass(cfg, dr, oracle, queries)),
            ("forced regimes", lambda: _forced_pass(
                cfg, index, dr, oracle, queries[:cfg.forced_batch])),
            ("engine", lambda: _engine_pass(
                cfg, index, dr, oracle, queries[:cfg.engine_batch])),
            ("variants", lambda: _variant_pass(
                cfg, queries[:cfg.forced_batch])))
        with _KernelCalls() as kc:
            for name, phase in phases:
                t = time.perf_counter()
                phase()
                print(f"setup phase {name} took "
                      f"{time.perf_counter() - t:.1f} s", flush=True)
        for label in kc.targets:
            _check(label in kc.calls, f"kernel {label} ran")
            fn, a, kw = kc.calls[label]
            text = fn.lower(*a, **kw).compile().as_text()
            if label == "device fragment builder":
                continue                     # plain XLA, no Pallas kernel
            if dev.platform == "tpu":
                _check("tpu_custom_call" in text,
                       f"kernel {label} compiled for the chip")
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    stats = dev.memory_stats() or {}
    facts.update(compile_count=compiles.count,
                 compile_s=compiles.seconds,
                 peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    print(f"setup compiles {compiles.count} taking "
          f"{compiles.seconds:.1f} s", flush=True)
    print(f"setup peak_bytes_in_use {facts['peak_bytes_in_use']}",
          flush=True)
    return facts
