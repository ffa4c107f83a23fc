"""The trace reduction: on a small trace recorded on a TPU v5e, and on a
hand-made trace whose answers are known."""

import os
from types import SimpleNamespace as NS

import pytest

from bench.trace import WINDOW, reduce_file, reduce_profile
from bench.tests.conftest import ROOT

# the window of a touche-offline run at 20,000 documents on one TPU v5e
# (6 batches of 64 queries), recorded by bench/record_trace.py
RECORDED = os.path.join(ROOT, "bench", "testdata",
                        "touche-offline-20000-docs.xplane.pb")


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def _profile(window, ops, modules=(), host=()):
    host_line = NS(name="python3", events=[_ev(WINDOW, *window)]
                   + [_ev(n, s, d) for n, s, d in host])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev(n, s, d) for n, s, d in modules]),
        NS(name="XLA Ops", events=[_ev(n, s, d) for n, s, d in ops])])
    return NS(planes=[NS(name="/host:CPU", lines=[host_line]), dev])


def test_busy_union_clipping_keys_and_gaps():
    p = _profile(
        window=(1000, 9000),
        modules=[("jit_build_fragment_table(7)", 900, 3100),
                 ("jit_bm25_retrieve_resident(8)", 5000, 3000)],
        ops=[("while.1", 900, 2600),         # clipped to [1000, 3500)
             ("fusion.3", 1200, 800),        # nested in the while
             ("bm25_resident_score_topk_db", 5000, 2000),
             ("fusion.12", 7000, 500)],
        host=[("bench.pack", 3500, 1500), ("bench.retrieve", 7500, 3000)])
    t = reduce_profile(p)
    assert t.window_s == pytest.approx(9e-6)
    assert t.busy_s == pytest.approx((2500 + 2500) / 1e9)
    assert t.seconds(r"build_fragment_table") == pytest.approx(2500 / 1e9)
    assert t.seconds(r"while") == pytest.approx(1700 / 1e9)
    assert t.op_seconds() == pytest.approx(t.busy_s)
    assert t.seconds(r"bm25_\w*score\w*topk") == pytest.approx(2e-6)
    keys = {k for k, _ in t.breakdown()["device_ops"]}
    assert keys == {"jit_build_fragment_table:fusion",
                    "jit_build_fragment_table:while",
                    "jit_bm25_retrieve_resident:bm25_resident_score_topk_db",
                    "jit_bm25_retrieve_resident:fusion"}
    gaps = t.breakdown()["idle_gaps"]
    assert gaps[0] == ["bench.retrieve", pytest.approx(2.5e-6)]
    assert gaps[1] == ["bench.pack", pytest.approx(1.5e-6)]


def test_no_window_no_trace():
    p = _profile(window=(0, 10), ops=[("fusion", 0, 5)])
    p.planes[0].lines[0].events[0].name = "other"
    assert reduce_profile(p) is None


def test_recorded_chip_trace():
    t = reduce_file(RECORDED)
    assert t is not None and list(t.ops) == ["/device:TPU:0"]
    assert 0 < t.busy_s <= t.window_s
    # nested ops count once: self times add up to the busy union
    assert t.op_seconds() == pytest.approx(t.busy_s, rel=1e-9)
    frag = t.seconds(r"build_fragment_table")
    kernel = t.seconds(r"bm25_\w*score\w*topk")
    assert frag > 0 and kernel > 0
    assert frag + kernel <= t.busy_s
    assert t.seconds(r":bm25_resident_score_topk_db$") == kernel
    b = t.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in b["device_ops"])
    assert all(":" in k and " " not in k for k, _ in b["device_ops"])
