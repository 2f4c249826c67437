"""The large-template cell ``g500-s16.u12-closed``: its files load by name
with the metrics it reports, and ``correct``, rehearsed on the CPU at the
configuration's tiny scale, is true for the program as configured and false
for the control (the program's own bfloat16 storage path).

The same comparison on the chip, at the cell's own size, is
``bench/readings.py --workload g500-s16.u12-closed``.
"""

import time

from bench.harness import cell as cells
from bench.harness import runner

CELL = "g500-s16.u12-closed"
SECONDS = 2.0


def run(seed, **kw):
    return runner.run(CELL, seed, SECONDS, False, time.perf_counter(),
                      rehearsal=True, log=lambda *a: None, **kw)


def test_cell_loads_with_its_metrics():
    c = cells.load(CELL)
    assert c.chips == 1
    assert c.config["name"] == "g500-s16"
    assert c.config["templates"]["u12"]["send"] == "u12"
    assert c.traffic["templates"] == ["u12"]
    assert [m.name for m in c.end_to_end] == ["colorings_per_s", "setup_s"]
    assert [m.name for m in c.per_layer] == [
        "device_s_per_coloring", "plan_roofline", "device_idle_share.closed",
        "spmm_s_per_coloring", "ema_s_per_coloring",
        "service_idle_s_per_request"]


def test_template_is_the_registry_u12():
    from repro.core import get_template
    spec = cells.load(CELL).config["templates"]["u12"]
    t = get_template("u12")
    assert sorted(tuple(sorted(e)) for e in spec["edges"]) == \
        sorted(t.edges)
    assert spec["root"] == t.root


def test_program_is_correct():
    out = run(41)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["rehearsal"] is True


def test_control_is_not_correct():
    out = run(42, dtype="bfloat16")
    assert not out["correct"]
    c = out["checks"]["estimate_rel_gap"]
    assert c["value"] > c["limit"]
