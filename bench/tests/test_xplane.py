"""The ``tf_op`` decoder and the reductions by device scope and host span."""

import importlib.util
import os
import re

import pytest

from bench.harness import trace, xplane
from bench.harness.cell import BENCH_DIR
from bench.harness.runner import RunData
from bench.harness.traffic import Request, Window

DATA = os.path.join(os.path.dirname(__file__), "data")
SEGMENT_SUM = os.path.join(DATA, "tpu_segment_sum.xplane.pb")
SCOPED = os.path.join(DATA, "tpu_scoped_u5.xplane.pb")


def _op_events(path):
    from jax.profiler import ProfileData

    return {plane.name: {ev.name for line in plane.lines
                         if line.name == trace.OPS_LINE
                         for ev in line.events}
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith(trace.DEVICE_PLANE_PREFIX)}


def test_decoder_gives_every_recorded_op_its_tf_op():
    tf = xplane.tf_ops(SEGMENT_SUM)
    events = _op_events(SEGMENT_SUM)
    assert set(tf) == set(events) == {"/device:TPU:0"}
    ops = tf["/device:TPU:0"]
    assert events["/device:TPU:0"] <= set(ops)
    assert set(ops.values()) == {"jit(<lambda>)/gather:",
                                 "jit(<lambda>)/scatter-add:", "t:"}
    fusion = next(n for n in ops if n.startswith("%fusion.1 "))
    assert ops[fusion] == "jit(<lambda>)/scatter-add:"


@pytest.mark.parametrize("tf_op, key", [
    ("jit(seeded)/plan.node3/kernel.spmm/while/body/scatter-add:",
     "plan.node3/kernel.spmm"),
    ("jit(seeded)/vmap(plan.node12)/kernel.ema/while/body/gather:",
     "plan.node12/kernel.ema"),
    ("jit(seeded)/plan.node4/kernel.ema/kernel.spmm/gather:",
     "plan.node4/kernel.spmm"),
    ("jit(seeded)/kernel.root/reduce_sum:", "kernel.root"),
    ("jit(seeded)/plan.node2/broadcast_in_dim:", "plan.node2"),
    ("jit(<lambda>)/scatter-add:", "unscoped"),
    ("", "unscoped"),
])
def test_scope_key_takes_the_innermost_node_and_kernel(tf_op, key):
    assert xplane.scope_key(tf_op) == key


def test_reduce_scoped_on_plain_data():
    ops = {"d": [("w", 0.0, 10.0), ("s1", 1.0, 4.0), ("e1", 4.0, 5.0),
                 ("r", 6.0, 7.0), ("c", 10.5, 12.0)]}
    tf = {"d": {"w": "jit(f)/plan.node3/while:",
                "s1": "jit(f)/plan.node3/kernel.spmm/scatter-add:",
                "e1": "jit(f)/plan.node3/kernel.ema/gather:",
                "r": "jit(f)/kernel.root/reduce_sum:"}}
    r = xplane.reduce_scoped(ops, [("bench.window", 0.0, 12.0)],
                             (0.0, 11.0), tf)
    # self times, clipped to the window: the while keeps what its body's
    # ops leave (10 - 3 - 1 - 1); "c" has no tf_op
    assert r["device_scopes"] == pytest.approx({
        "plan.node3": 5.0, "plan.node3/kernel.spmm": 3.0,
        "plan.node3/kernel.ema": 1.0, "kernel.root": 1.0, "unscoped": 0.5})
    assert list(r["device_scopes"])[0] == "plan.node3"
    assert r["idle_by_span"] == pytest.approx({"no host span": 0.5})


def test_idle_by_span_sums_every_gap_by_label():
    ops = {"d": [("s1", 1.0, 4.0), ("r", 6.0, 7.0)]}
    # gaps (0, 1), (4, 6), (7, 10); at 8.5 the later-started span wins
    host = [("service.idle", 3.5, 6.5), ("service.idle", 6.5, 9.0),
            ("frontend.respond", 7.0, 9.5), ("bench.window", 0.0, 10.0)]
    r = xplane.reduce_scoped(ops, host, (0.0, 10.0), {})
    assert r["idle_by_span"] == pytest.approx(
        {"frontend.respond": 3.0, "service.idle": 2.0, "no host span": 1.0})
    assert list(r["idle_by_span"]) == ["frontend.respond", "service.idle",
                                       "no host span"]
    assert r["device_scopes"] == pytest.approx({"unscoped": 4.0})


def test_recorded_trace_reduction_keeps_its_keys():
    """Reading the scopes leaves ``read_xplane``'s reduction as it was."""
    r = trace.read_xplane(SEGMENT_SUM)
    assert set(r) == {"busy_s", "window_s", "device_ops", "idle_gaps"}
    s = xplane.read_scoped(SEGMENT_SUM)
    assert set(s) == {"device_scopes", "idle_by_span"}
    assert sum(s["device_scopes"].values()) == pytest.approx(r["busy_s"])
    assert sum(s["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_recorded_scoped_tpu_trace():
    """Two dispatches of a u5 engine (512 vertices, batch 2) recorded on a
    TPU v5e chip under ``tracing.profile``, a 4 ms ``host.pause`` span
    after each, in a ``bench.window`` annotation. The recording's
    ``/host:metadata`` plane (HLO protos, which no reduction reads) was
    dropped to keep the file small."""
    tf = xplane.tf_ops(SCOPED)["/device:TPU:0"]
    events = _op_events(SCOPED)["/device:TPU:0"]
    # XLA's own ops (loops, copies it inserts, buffer placeholders) carry
    # no op_name, so the profiler gives them no tf_op
    for name in events - set(tf):
        assert re.search(r" = .*?\s(while|copy|copy-start|copy-done|"
                         r"custom-call)\(", name), name
    assert len(tf) > len(events) / 2
    r = trace.read_xplane(SCOPED)
    s = xplane.read_scoped(SCOPED)
    scopes = s["device_scopes"]
    spmm = {k: v for k, v in scopes.items()
            if k.startswith("plan.node") and k.endswith("/kernel.spmm")}
    assert len(spmm) == 2 and sum(spmm.values()) > 0.5 * r["busy_s"]
    assert {"kernel.leaf", "kernel.root"} <= set(scopes)
    assert any(k.endswith("/kernel.ema") for k in scopes)
    assert sum(scopes.values()) == pytest.approx(r["busy_s"])
    # the pauses hold most of the idle time; the gaps' labels come from the
    # program's spans on the profiler clock
    assert list(s["idle_by_span"])[0] == "host.pause"
    assert s["idle_by_span"]["host.pause"] > 0.008
    assert sum(s["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH_DIR / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _request(idx, t_send, status="done", **answer):
    r = Request(idx, "u7", idx, "batch", None, 2, t_send=t_send,
                t_done=t_send + 0.2, status=status)
    if status == "done":
        r.answer = {"iterations": 2, "estimate": 1.0, **answer}
    return r


def _run(traced: bool) -> RunData:
    reqs = [_request(0, 10.1), _request(1, 10.4),
            _request(2, 10.7, from_cache=True),      # no colorings of its own
            _request(3, 9.0),                        # sent before the trace
            _request(4, 10.9, status="failed")]
    trace_ = {
        "started": 10.0, "stopped": 11.0, "busy_s": 0.555,
        "window_s": 1.0,
        "device_scopes": {"plan.node3/kernel.spmm": 0.3,
                          "plan.node1/kernel.spmm": 0.2,
                          "plan.node3/kernel.ema": 0.03,
                          "plan.node1/kernel.ema": 0.01,
                          "kernel.root": 0.01, "unscoped": 0.005},
        "idle_by_span": {"service.idle": 0.3, "service.retire": 0.12,
                         "no host span": 0.03}}
    return RunData(20.0, Window(reqs, 9.0, 11.0, 11.1),
                   trace_ if traced else None, None)


@pytest.mark.parametrize("metric, value", [
    # 2 requests of 2 colorings drew their own samples in the trace
    ("spmm_s_per_coloring", (0.3 + 0.2) / 4),
    ("ema_s_per_coloring", (0.03 + 0.01) / 4),
    # 3 requests answered in the trace; waiting for work is not counted
    ("service_idle_s_per_request", (0.12 + 0.03) / 3),
])
def test_scope_readers(metric, value):
    read = _reader(metric)
    assert read(_run(True)) == pytest.approx(value)
    assert read(_run(False)) is None
