"""``correct`` on a whole run, rehearsed on the CPU at a tiny scale: true
for the program as configured, false for the control (the program's own
bfloat16 storage path) and for each fault the cell can have, planted in the
timed path underneath the harness.

The same comparison on the chip, at the cells' own sizes, is
``bench/readings.py``.
"""

import time

import numpy as np
import pytest

from bench.harness import runner
from repro.core.engines import CountingEngine

CELL = "g500-s18.u7-closed"
SECONDS = 2.0


def run(seed, **kw):
    return runner.run(CELL, seed, SECONDS, False, time.perf_counter(),
                      rehearsal=True, log=lambda *a: None, **kw)


def test_program_is_correct():
    out = run(31)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["rehearsal"] is True
    assert list(out)[-1] == "checks"


def test_control_is_not_correct():
    out = run(32, dtype="bfloat16")
    assert not out["correct"]
    c = out["checks"]["estimate_rel_gap"]
    assert c["value"] > c["limit"]


_orig = CountingEngine.count_iterations_batch


def _answer_altered(self, iterations, seed=0, batch_size=None):
    out = _orig(self, iterations, seed, batch_size)
    if 0 in out:
        out[0] = out[0] * 1.01
    return out


def _half_batch(self, iterations, seed=0, batch_size=None):
    its = [int(i) for i in iterations]
    kept = _orig(self, its[: max(1, len(its) // 2)], seed, batch_size)
    mean = float(np.mean(list(kept.values())))
    return {i: kept.get(i, mean) for i in its}


def _state_unchanged():
    last = []

    def counter(self, iterations, seed=0, batch_size=None):
        out = _orig(self, iterations, seed, batch_size)
        if last and len(last[0]) == len(out):
            out = dict(zip(out, last[0]))
        last[:] = [list(out.values())]
        return out

    return counter


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch",
                                   "state_unchanged"])
def test_fault_is_not_correct(monkeypatch, fault):
    fn = {"answer_altered": _answer_altered, "half_batch": _half_batch,
          "state_unchanged": _state_unchanged()}[fault]
    monkeypatch.setattr(CountingEngine, "count_iterations_batch", fn)
    out = run(33)
    assert not out["correct"], out["checks"]
