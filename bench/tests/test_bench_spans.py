"""The readers of the program's own spans and counters (``repro.obs``): a
traced run of the bulk cell reads every one, and each reads nothing
where the program's records are not the window's batches."""

import functools
import os
import sys
import time

import pytest

from bench.harness import BatchRecord, Context, _reader, run_cell
from bench.tests.conftest import ROOT, TINY
from bench.trace import reduce_file

# the window of a touche-offline run at 20,000 documents on one TPU v5e
# (12 batches of 64 queries), with the program's spans, recorded by
# bench/record_trace.py
RECORDED = os.path.join(ROOT, "bench", "testdata",
                        "touche-offline-20000-docs-spans.xplane.pb")
PROGRAM_SPANS = {"retriever.pack", "retriever.retrieve", "retriever.plan",
                 "fragments.build", "fragments.overflow_wait",
                 "kernel.dispatch", "board.wait", "board.finish"}

READERS = ("host_ms_per_batch.offline", "plan_ms.offline",
           "readback_wait_max_ms.offline",
           "fragment_builds_per_batch.offline", "stream_fill.offline",
           "fragment_fill.offline")


def test_traced_bulk_cell_reads_every_span_and_counter(monkeypatch,
                                                       test_root):
    """On the chip's serving path (resident gather, fragment tables built
    on the device), spelled out here because the CPU defaults to the host
    gather."""
    import repro.serve
    monkeypatch.setattr(repro.serve, "DeviceRetriever", functools.partial(
        repro.serve.DeviceRetriever, gather="resident", plan="device"))
    r = run_cell(test_root, "touche-offline", 2_147_483_671, 1.0, True,
                 t_start=time.perf_counter(), require_tpu=False,
                 shape_override=TINY)
    assert r["correct"] is True, r["checks"]
    m = {name: r["metrics"][name]["value"] for name in READERS}
    assert m["host_ms_per_batch.offline"] > m["plan_ms.offline"] > 0
    assert m["readback_wait_max_ms.offline"] > 0
    assert m["fragment_builds_per_batch.offline"] >= 1.0
    # Σ df over its own pow2 bucket: more than half, at most all
    assert 50.0 < m["stream_fill.offline"] <= 100.0
    assert 0.0 < m["fragment_fill.offline"] <= 100.0


def _window(n_batches, sum_df=1000):
    """``n_batches`` program records made as the retriever makes them,
    and the benchmark's record of the same batches."""
    from repro import obs
    ctx = Context(kind="batch")
    for i in range(n_batches):
        with obs.batch():
            with obs.span("retriever.pack"):
                pass
            with obs.span("retriever.retrieve"):
                with obs.span("retriever.plan"):
                    obs.count("sum_df", sum_df + i)
                with obs.span("fragments.overflow_wait"):
                    pass
                with obs.span("board.wait"):
                    pass
            obs.count("frag_builds", 1)
            obs.count("stream_positions", 2048)
            obs.count("frags", 3)
            obs.count("frag_slots", 8)
        ctx.batches.append(BatchRecord(64, 0.0, sum_df + i, sum_df + i,
                                       "gathered", False))
    return ctx


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_unless_the_records_are_the_window(
        name, monkeypatch):
    read = _reader(f"{ROOT}/bench", name)
    ctx = _window(3)
    assert read(ctx) is not None
    if name == "stream_fill.offline":
        assert read(ctx) == pytest.approx(100.0 * 3003 / (3 * 2048))
    if name == "fragment_fill.offline":
        assert read(ctx) == pytest.approx(100.0 * 3 / 8)
    # a batch the records do not hold, or hold out of order
    off = _window(3)
    off.batches[1].plan_sum_df += 1
    assert read(off) is None
    swapped = _window(2)
    swapped.batches.reverse()
    assert read(swapped) is None
    # more batches than the records end with, and no batches at all
    short = _window(2)
    short.batches.insert(0, BatchRecord(64, 0.0, 7, 7, "gathered", False))
    assert read(short) is None
    assert read(Context(kind="batch")) is None
    # a program that keeps no records
    import repro
    ctx = _window(3)
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(ctx) is None


def test_recorded_trace_names_idle_gaps_by_program_spans():
    """The benchmark's own annotations span whole calls; the program's
    spans lie inside them, so the reduction (the shortest host event over
    a gap's middle) names the gaps by what the program was doing. The
    rest are the JAX runtime's own events inside a read-back, or host
    code outside any event."""
    t = reduce_file(RECORDED)
    labels = [name for name, _ in t.breakdown()["idle_gaps"]]
    assert len(labels) == 10
    assert not any(name.startswith("bench.") for name in labels)
    assert len(PROGRAM_SPANS.intersection(labels)) >= 3
    assert sum(name in PROGRAM_SPANS for name in labels) >= 4
