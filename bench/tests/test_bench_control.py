"""``correct`` has to come out false where the timed path is broken.

At a tiny size on the CPU: the control (the reference itself in bfloat16,
one step below the configuration's float32 scores, put in the program's
place) and each fault a retrieval cell can have, planted underneath a
whole run that skips only the harness's look for a chip.
"""

import time

import ml_dtypes
import numpy as np
import pytest

from bench import check
from bench.harness import Bench, load_cell, run_cell
from bench.tests.conftest import TINY

SEED = 2_147_483_777


def _run(root, workload):
    return run_cell(root, workload, SEED, 1.0, False,
                    t_start=time.perf_counter(), require_tpu=False,
                    shape_override=TINY)


@pytest.mark.parametrize("workload", ["touche-offline", "quora-online"])
def test_control_fails(workload, test_root):
    bench = Bench(test_root, load_cell(test_root, workload), SEED,
                  require_tpu=False, shape_override=TINY)
    try:
        plan = bench.plan(1.0)
        ctx = bench.window(plan, 1.0)
        bench.free_program()
        sample = bench.sample(ctx)
        assert check.passed(check.compare(bench.cfg, bench.corpus, sample,
                                          plan.k))
        low = check.reference_for(bench.cfg, bench.corpus,
                                  [a.query for a in sample],
                                  dtype=ml_dtypes.bfloat16)
        control = []
        for a in sample:
            ids, vals = low.top_k(a.query, plan.k)
            control.append(check.Answer(a.query, ids,
                                        vals.astype(np.float32)))
        checks = check.compare(bench.cfg, bench.corpus, control, plan.k)
    finally:
        bench.close()
    assert not check.passed(checks)
    assert checks["score_err"]["value"] > 10 * checks["score_err"]["limit"]


def _altered(res):
    """One score of every board nudged where the retriever produces it."""
    res.scores = np.array(res.scores, copy=True)
    res.scores[..., -1] *= np.float32(1.001)
    return res


def _half_left_out(res):
    """The second half of the batch answered with the first half's boards."""
    b = len(res.ids)
    ids, sc = np.array(res.ids), np.array(res.scores)
    ids[b // 2:], sc[b // 2:] = ids[:b - b // 2], sc[:b - b // 2]
    res.ids, res.scores = ids, sc
    return res


@pytest.mark.parametrize("workload", ["touche-offline", "quora-online"])
@pytest.mark.parametrize("fault", [_altered, _half_left_out])
def test_broken_retrieval_is_not_correct(workload, fault, monkeypatch,
                                        test_root):
    from repro.serve import DeviceRetriever
    real = DeviceRetriever.retrieve_batch

    def broken(self, *a, **kw):
        return fault(real(self, *a, **kw))
    monkeypatch.setattr(DeviceRetriever, "retrieve_batch", broken)
    r = _run(test_root, workload)
    assert r["correct"] is False, r["checks"]


@pytest.mark.parametrize("workload", ["touche-offline", "quora-online"])
def test_broken_index_build_is_not_correct(workload, monkeypatch,
                                          test_root):
    """An eager score off by 1e-3 in the index build (the part the
    program's own oracle reads back instead of checking)."""
    import repro.core
    real = repro.core.build_index

    def broken(*a, **kw):
        index = real(*a, **kw)
        index.scores[: index.scores.size // 2] *= np.float32(1.001)
        return index
    monkeypatch.setattr(repro.core, "build_index", broken)
    r = _run(test_root, workload)
    assert r["correct"] is False, r["checks"]
