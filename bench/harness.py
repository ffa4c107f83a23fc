"""One run of one benchmark cell: set-up, measured window, check, report.

Everything about a cell is found by name from ``BENCHMARK.json``:

* the configuration's file (``configs[].file``): published sizes, scoring
  parameters, the reference's name and the compared numbers' limits;
* the traffic mix, ``bench/traffic/<traffic>.json``, read by the one
  generator in :mod:`bench.traffic`;
* each per-layer metric's reader, ``bench/metrics/<name>.py``, a
  ``read(ctx)`` that returns a number or None (nothing to read: the
  metric is left out of the line).

So a later change adds a cell, a mix or a metric by adding files and
entries, and edits none. The system under test is only what the program
exports: ``build_index``, ``DeviceRetriever`` (``pack_batch`` /
``retrieve_batch``) and ``ServingFrontend``, their counters and timers,
and the names of its device programs and kernels in the trace.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

from . import check
from .corpus import Shape, make_corpus
from .trace import WINDOW
from .traffic import plan as make_plan


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class WindowCompiled(RuntimeError):
    """Something compiled, or loaded from the compile cache, inside every
    one of ``WINDOW_TRIES`` windows: the warm-up misses shapes the traffic
    keeps using."""


WINDOW_TRIES = 3


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    dir: str              # the benchmark's directory (``paths[0]``)


def load_cell(root: str, workload: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise ValueError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg_file = {c["name"]: c["file"] for c in spec["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_file)) as f:
        config = json.load(f)
    bench_dir = os.path.join(root, spec["paths"][0])
    with open(os.path.join(bench_dir, "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def ours(m):
        return workload in m.get("workloads", [workload])
    e2e = [m for m in spec["end_to_end"] if ours(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if ours(m) and m["moves"] in reported]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer,
                bench_dir)


def _reader(bench_dir: str, name: str):
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileLog:
    """Counts backend compilations and persistent-cache loads from JAX's
    monitoring events."""

    def __init__(self):
        self.counts: dict[str, int] = {}

    def __call__(self, event, duration, **_):
        if "backend_compile" in event or "cache_retrieval" in event:
            self.counts[event] = self.counts.get(event, 0) + 1

    def total(self) -> int:
        return sum(self.counts.values())


@dataclass
class BatchRecord:
    n: int
    pack_s: float
    sum_df: int          # df of the batch's distinct tokens (benchmark's)
    plan_sum_df: int     # the planner's RetrievalPlan.sum_df
    regime: str
    degraded: bool


@dataclass
class Context:
    """One window's record; what a per-layer metric's reader may read."""

    kind: str
    queries: int = 0              # answered (batch) or due (open loop)
    failed: int = 0
    window_s: float = 0.0
    metrics: dict = field(default_factory=dict)   # end to end
    answers: list = field(default_factory=list)
    batches: list = field(default_factory=list)   # batch kind
    frontend: dict | None = None                  # open loop: health()
    gen_lag_s: np.ndarray | None = None           # open loop
    latency_s: np.ndarray | None = None           # open loop
    close_s: float = 0.0                          # open loop: last send
    compiles: dict = field(default_factory=dict)
    trace: object = None
    peak: dict = field(default_factory=dict)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _setup_jax(root: str, chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    cache = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or os.path.join(root, ".jax_cache"))
    os.makedirs(cache, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"setup device {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}, compile cache {cache}")
    return devices[:chips]


def _sum_df(df: np.ndarray, queries) -> int:
    return int(df[np.unique(np.concatenate(queries))].sum())


def _warm(dr, df, groups, k: int, compiles: CompileLog,
          max_passes: int = 4) -> tuple[int, int, int]:
    """Run the traffic's batches until a pass compiles nothing.

    The batches are grouped by the shapes of the arrays that the
    program's ``pack_batch`` hands the device and by their posting work
    to within a factor of two; of each group the lightest and the
    heaviest run, in the order the window sends them. What else a batch
    compiles under (the retriever's own buckets of its work, and the
    state one batch leaves the next) is found by running, not assumed:
    the pass repeats until it adds no compile event, and a window that
    still compiles is not measured (see :func:`run_cell`). Returns the
    number of groups, of batches per pass and of passes."""
    ends: dict[tuple, tuple] = {}
    for pos, g in enumerate(groups):
        p = dr.pack_batch(g)
        w = (_sum_df(df, g), pos)
        key = (tuple(np.shape(a) for a in (p.uniq_tab, p.weights, p.shift)),
               w[0].bit_length())
        lo, hi = ends.get(key, (w, w))
        ends[key] = (min(lo, w), max(hi, w))
    reps = sorted({pos for pair in ends.values() for _, pos in pair})
    for n in range(1, max_passes + 1):
        c0 = compiles.total()
        for pos in reps:
            dr.retrieve_batch(groups[pos], k)
        if compiles.total() == c0:
            break
    return len(ends), len(reps), n


def _offline_groups(plan):
    b = plan.batch
    return [plan.queries[i:i + b] for i in range(0, len(plan.queries), b)]


def _online_groups(plan, max_batch: int, starts: int = 1024):
    """Contiguous runs of the arrival order of every length the frontend
    can form, from ``starts`` evenly spaced positions."""
    qs = plan.queries
    step = max(1, len(qs) // starts)
    return [qs[i:i + n] for i in range(0, len(qs), step)
            for n in range(1, max_batch + 1) if i + n <= len(qs)]


def _run_offline(dr, plan, df, seconds, ctx):
    batches = _offline_groups(plan)
    work = [_sum_df(df, g) for g in batches]
    answers: dict[int, check.Answer] = {}
    import jax.profiler as jp
    t0 = time.perf_counter()
    deadline, i = t0 + seconds, 0
    while i == 0 or time.perf_counter() < deadline:
        j = i % len(batches)
        qs = batches[j]
        with jp.TraceAnnotation("bench.pack"):
            packed = dr.pack_batch(qs)
        with jp.TraceAnnotation("bench.retrieve"):
            res = dr.retrieve_batch(None, plan.k, packed=packed)
        ctx.batches.append(BatchRecord(
            len(qs), packed.pack_s, work[j], res.plan.sum_df,
            res.plan.regime, bool(res.degraded)))
        if j * plan.batch not in answers:
            for r, q in enumerate(qs):
                answers[j * plan.batch + r] = check.Answer(
                    q, np.asarray(res.ids[r]), np.asarray(res.scores[r]))
        i += 1
    t1 = time.perf_counter()
    ctx.queries = sum(b.n for b in ctx.batches)
    ctx.window_s = t1 - t0
    ctx.failed = sum(b.n for b in ctx.batches if b.degraded)
    ctx.metrics = {"qps": (ctx.queries / (t1 - t0), "queries/s")}
    ctx.answers = [answers[i] for i in sorted(answers)]


def _run_online(dr, plan, ctx, traffic):
    from repro.serve import ServingFrontend
    from repro.serve.errors import RetrievalError
    import jax.profiler as jp
    n = len(plan.queries)
    done = np.full(n, np.nan)
    sent = np.zeros(n)
    futs = [None] * n

    def stamp(i):
        def cb(_f):
            done[i] = time.perf_counter()
        return cb

    fe = ServingFrontend(dr, k=plan.k,
                         max_batch=int(traffic["max_batch"]),
                         batch_deadline_s=float(
                             traffic["batch_deadline_ms"]) / 1e3)
    try:
        t0 = time.perf_counter()
        due = t0 + 0.005 + plan.due_s
        for i, q in enumerate(plan.queries):
            wait = due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent[i] = time.perf_counter()
            try:
                with jp.TraceAnnotation("bench.submit"):
                    f = fe.submit(q)
            except RetrievalError:    # refused at the door: failed below
                continue
            f.add_done_callback(stamp(i))
            futs[i] = f
        close = time.perf_counter()
        for f in futs:
            if f is not None:
                try:
                    f.result(timeout=max(0.0, close + 60.0
                                         - time.perf_counter()))
                except Exception:     # noqa: BLE001 - counted as failed
                    pass
        t1 = time.perf_counter()
        ctx.frontend = fe.health()
    finally:
        fe.close(drain=False)
    answers, failed = [], 0
    for q, f in zip(plan.queries, futs):
        r = None
        if f is not None and f.done() and f.exception() is None:
            r = f.result()
        if r is None or r.degraded:
            failed += 1
        answers.append(check.Answer(
            q, None if r is None else np.asarray(r.ids),
            None if r is None else np.asarray(r.scores)))
    # a request that never came waited at least until the check gave up
    lat = np.where(np.isnan(done), t1, done) - due
    ctx.queries = n
    ctx.window_s = t1 - t0
    ctx.failed = failed
    ctx.gen_lag_s = sent - due
    ctx.latency_s = lat
    ctx.close_s = close - t0
    ctx.metrics = {"p50_ms": (1e3 * float(np.percentile(lat, 50)), "ms"),
                   "p95_ms": (1e3 * float(np.percentile(lat, 95)), "ms")}
    ctx.answers = answers


def _override(cfg: dict, shape_override: dict | None) -> dict:
    if not shape_override:
        return dict(cfg)
    return {**cfg, **{k: v for k, v in shape_override.items()
                      if k != "assumed"},
            "assumed": {**cfg["assumed"],
                        **shape_override.get("assumed", {})}}


class Bench:
    """One cell's system under test, built from the seed; reused by the
    knee sweep and the control readings for several windows."""

    def __init__(self, root: str, cell: Cell, seed: int, *,
                 require_tpu: bool = True,
                 shape_override: dict | None = None):
        self.cell, self.seed = cell, seed
        self.devices = _setup_jax(root, cell.chips, require_tpu)
        import jax.monitoring
        from repro.core import BM25Params, build_index
        from repro.serve import DeviceRetriever
        self.compiles = CompileLog()
        jax.monitoring.register_event_duration_secs_listener(self.compiles)
        self.cfg = cfg = _override(cell.config, shape_override)
        self.shape = shape = Shape.from_config(cfg)
        t = time.perf_counter()
        self.corpus = make_corpus(shape, seed)
        log(f"setup corpus {shape.n_docs} docs, "
            f"{self.corpus.tokens.size} content tokens, "
            f"{time.perf_counter() - t:.2f} s")
        t = time.perf_counter()
        self.index = build_index(
            self.corpus.documents(), shape.n_vocab,
            params=BM25Params(method=cfg["method"], k1=float(cfg["k1"]),
                              b=float(cfg["b"])))
        log(f"setup build_index nnz {self.index.nnz}, "
            f"{time.perf_counter() - t:.2f} s")
        t = time.perf_counter()
        self.dr = DeviceRetriever(self.index)
        self.df = np.diff(self.index.indptr)
        log(f"setup DeviceRetriever gather {self.dr.gather_mode} plan "
            f"{self.dr.plan_mode}, {time.perf_counter() - t:.2f} s")

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self.compiles)

    def plan(self, seconds: float, traffic: dict | None = None):
        return make_plan(traffic or self.cell.traffic, self.shape,
                         self.seed, seconds)

    def warm(self, plan, traffic: dict | None = None) -> None:
        traffic = traffic or self.cell.traffic
        t, c0 = time.perf_counter(), self.compiles.total()
        if plan.kind == "batch":
            groups = _offline_groups(plan)
        else:
            groups = _online_groups(plan, int(traffic["max_batch"]))
        n_keys, n_reps, passes = _warm(self.dr, self.df, groups, plan.k,
                                       self.compiles)
        log(f"setup warm-up {n_keys} groups, {n_reps} batches x "
            f"{passes} passes, {self.compiles.total() - c0} compile "
            f"events, {time.perf_counter() - t:.2f} s")

    def window(self, plan, seconds: float, *, traffic: dict | None = None,
               trace_dir: str | None = None) -> Context:
        """The measured window; with ``trace_dir`` under the profiler."""
        import jax.profiler as jp
        traffic = traffic or self.cell.traffic
        ctx = Context(kind=plan.kind)
        before = dict(self.compiles.counts)
        gc.collect()
        gc.freeze()
        if trace_dir:
            opts = jp.ProfileOptions()
            opts.python_tracer_level = 0
            jp.start_trace(trace_dir, profiler_options=opts)
        try:
            with (jp.TraceAnnotation(WINDOW) if trace_dir
                  else contextlib.nullcontext()):
                if plan.kind == "batch":
                    _run_offline(self.dr, plan, self.df, seconds, ctx)
                else:
                    _run_online(self.dr, plan, ctx, traffic)
        finally:
            if trace_dir:
                jp.stop_trace()
            gc.unfreeze()
        ctx.compiles = {e.rsplit("/", 1)[-1]: n - before.get(e, 0)
                        for e, n in self.compiles.counts.items()
                        if n > before.get(e, 0)}
        nb = len(ctx.batches) or (ctx.frontend or {}).get("batches")
        log(f"window {ctx.window_s:.3f} s, {ctx.queries} queries, {nb} "
            f"batches, compile events in window: {ctx.compiles or 'none'}")
        if ctx.batches:
            regimes = {}
            for b in ctx.batches:
                regimes[b.regime] = regimes.get(b.regime, 0) + 1
            log(f"window regimes {regimes}")
        return ctx

    def memory_peak(self) -> int:
        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in self.devices))

    def free_program(self) -> None:
        """Drop the system under test's state before the reference runs."""
        self.dr = self.index = None
        gc.collect()

    def sample(self, ctx) -> list:
        work = [int(self.df[a.query].sum()) for a in ctx.answers]
        return check.sample_answers(ctx.answers, np.asarray(work),
                                    int(self.cell.traffic["sample"]),
                                    self.seed)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_tpu: bool = True,
             shape_override: dict | None = None) -> dict:
    """One run; returns the result line's object. ``shape_override``
    replaces configuration sizes (tests run cells at a tiny size on the
    CPU). Raises :class:`WindowCompiled` where no window ran free of
    compile events."""
    cell = load_cell(root, workload)
    bench = Bench(root, cell, seed, require_tpu=require_tpu,
                  shape_override=shape_override)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        plan = bench.plan(seconds)
        bench.warm(plan)
        # a window that compiled met a shape the warm-up missed (an open
        # loop's batches form by timing): it counts as warm-up, in
        # ``setup_s``, and the window runs again
        for _ in range(WINDOW_TRIES):
            setup_s = time.perf_counter() - t_start
            ctx = bench.window(plan, seconds, trace_dir=trace_dir)
            if not ctx.compiles:
                break
        else:
            raise WindowCompiled(f"compile events in {WINDOW_TRIES} "
                                 f"windows in a row: {ctx.compiles}")
        mem = bench.memory_peak()
        bench.free_program()
        t = time.perf_counter()
        sample = bench.sample(ctx)
        checks = check.compare(bench.cfg, bench.corpus, sample, plan.k)
        log(f"check {len(sample)} sampled answers against the reference, "
            f"{time.perf_counter() - t:.2f} s")
        devices = bench.devices
        result = {
            "correct": check.passed(checks),
            "attempted": int(ctx.queries),
            "failed": int(ctx.failed),
            "metrics": {},
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices),
                       "memory_peak_bytes": mem},
        }
        if trace:
            result["metrics"] = _per_layer(cell, ctx, trace_dir, devices,
                                           result)
        else:
            ctx.metrics["setup_s"] = (setup_s, "s")
            for m in cell.end_to_end:
                v, unit = ctx.metrics[m["name"]]
                result["metrics"][m["name"]] = {"value": v, "unit": unit}
        result["checks"] = checks
        for name, c in checks.items():
            log(f"check {name} {c['value']} limit {c['limit']}")
        return result
    finally:
        bench.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def _per_layer(cell, ctx, trace_dir, devices, result) -> dict:
    from .trace import find_xplane, reduce_file
    path = find_xplane(trace_dir)
    ctx.trace = reduce_file(path) if path else None
    with open(os.path.join(cell.dir, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    ctx.peak = peaks.get(devices[0].device_kind, {})
    out = {}
    for m in cell.per_layer:
        v = _reader(cell.dir, m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if ctx.trace is not None:
        result["device"]["busy_s"] = ctx.trace.busy_s
        result["device"]["window_s"] = ctx.trace.window_s
        result["breakdown"] = ctx.trace.breakdown()
    return out
