"""The trace reduction: busy/idle union, top ops, idle-gap labels."""

import glob
import os
import shutil

import pytest

from bench.harness import trace, xplane

SCOPED = os.path.join(os.path.dirname(__file__), "data",
                      "tpu_scoped_u5.xplane.pb")


def test_union_and_gaps_on_recorded_events():
    ops = {"/device:TPU:0": [("scatter", 1.0, 3.0), ("gather", 3.0, 4.0),
                             ("scatter", 6.0, 7.0), ("add", 9.5, 12.0)]}
    host = [("service.dispatch", 0.5, 4.5), ("engine.dispatch", 1.0, 4.0),
            ("runner.checkpoint", 4.2, 6.5), ("bench.window", 0.0, 10.0)]
    r = trace.reduce_events(ops, host, (0.0, 10.0))
    assert r["window_s"] == 10.0
    # union: [1,4] + [6,7] + [9.5,10] (clipped) = 3 + 1 + 0.5
    assert r["busy_s"] == pytest.approx(4.5)
    # op time is counted inside the window only
    assert r["device_ops"] == [["scatter", 3.0], ["gather", 1.0],
                               ["add", 0.5]]
    gaps = r["idle_gaps"]
    assert [g for _, g in gaps] == pytest.approx([2.5, 2.0, 1.0])
    # gap (7, 9.5): nothing but the window open; (4, 6): the checkpoint;
    # (0, 1): the dispatch span opened at 0.5
    assert [n for n, _ in gaps] == ["no host span", "runner.checkpoint",
                                    "service.dispatch"]


def test_self_time_and_short_names():
    hlo = ("%while.13 = (s32[]{:T(128)}, f32[35,2]{1,0}) while((s32[]) "
           "%t), body=%b")
    inner = ("%fusion.291 = f32[262144,2]{1,0:T(8,128)} fusion(s32[76]{0} "
             "%g), kind=kCustom")
    ops = {"d": [(hlo, 0.0, 10.0), (inner, 1.0, 4.0), (inner, 5.0, 9.0)]}
    r = trace.reduce_events(ops, [], (0.0, 10.0))
    assert r["busy_s"] == pytest.approx(10.0)
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.291 f32[262144,2] fusion": 7.0, "while.13 tuple while": 3.0})


def test_busy_is_averaged_over_devices():
    ops = {"a": [("x", 0.0, 2.0)], "b": [("x", 0.0, 4.0)]}
    r = trace.reduce_events(ops, [], (0.0, 4.0))
    assert r["busy_s"] == pytest.approx(3.0)


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    s = trace.Session(str(tmp_path), cpu=True)
    s.start()
    for _ in range(3):
        f(x).block_until_ready()
    s.stop()
    r = s.reduce()
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"]
    # the CPU's ops carry no tf_op: all their time is unscoped
    assert set(r["device_scopes"]) == {"unscoped"}
    assert sum(r["device_scopes"].values()) == pytest.approx(r["busy_s"])
    assert glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)


def test_recorded_tpu_trace():
    """A small trace recorded on a TPU v5e chip (segment sums inside a
    ``bench.window`` annotation)."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_segment_sum.xplane.pb")
    r = trace.read_xplane(path)
    assert 0 < r["busy_s"] < r["window_s"]
    ops = dict(r["device_ops"])
    assert ops["fusion.1 f32[4096,35] fusion"] > 0.9 * r["busy_s"]
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    # the three longest gaps are the host's sleeps between the steps
    assert [n for n, _ in r["idle_gaps"][:3]] == ["no host span"] * 3
    assert sum(g for _, g in r["idle_gaps"][:3]) > 0.015


def test_session_reduction_adds_scopes_and_keeps_the_rest(tmp_path):
    """A session's reduction of the recorded u5 trace: the four keys of
    :func:`trace.read_xplane`, with the values it gave before the scopes
    were read, and :func:`xplane.read_scoped`'s two over the same window."""
    profile = tmp_path / "plugins" / "profile" / "run"
    profile.mkdir(parents=True)
    shutil.copy(SCOPED, profile / "host.xplane.pb")
    r = trace.Session(str(tmp_path)).reduce()
    assert set(r) == {"busy_s", "window_s", "device_ops", "idle_gaps",
                      "device_scopes", "idle_by_span"}
    old = trace.read_xplane(SCOPED)
    assert {k: r[k] for k in old} == old
    assert r["busy_s"] == 0.00021861300000000639
    assert r["window_s"] == 0.01405356
    assert r["device_ops"][0] == ["fusion.9 f32[512,10] fusion",
                                  8.0087999999999e-05]
    assert r["idle_gaps"][0] == ["host.pause", 0.006775931999999998]
    scoped = xplane.read_scoped(SCOPED)
    assert r["device_scopes"] == scoped["device_scopes"]
    assert r["idle_by_span"] == scoped["idle_by_span"]
    assert sum(r["device_scopes"].values()) == pytest.approx(r["busy_s"])
