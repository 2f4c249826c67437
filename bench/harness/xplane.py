"""What ``jax.profiler.ProfileData`` leaves out of a trace: each device op's
``tf_op``, and the reductions that need it.

``ProfileData`` gives a device op's name (its HLO text) and times, but not
the event metadata's stats, where the TPU profiler keeps the op's
``tf_op``: the HLO ``op_name`` the program's ``jax.named_scope``\\ s built,
e.g. ``jit(seeded)/plan.node3/kernel.spmm/while/body/scatter-add:``. This
module decodes those stats from the ``.xplane.pb`` with the standard
library alone (the protobuf wire format), and reduces a window's device
time by scope and its idle time by host span:

* ``device_scopes``: self seconds keyed ``"plan.nodeN/kernel.x"``, from the
  innermost ``plan.node*`` and ``kernel.*`` components of each op's
  ``tf_op`` (either may be missing; an op with neither is ``"unscoped"``);
* ``idle_by_span``: the seconds of every idle gap of the window, by the
  label :func:`bench.harness.trace.label_gap` gives it.
"""

from __future__ import annotations

import re

from bench.harness.trace import (DEVICE_PLANE_PREFIX, gaps_between,
                                 label_gap, read_events, self_times,
                                 union_length)

TF_OP = "tf_op"
UNSCOPED = "unscoped"
_SCOPE = re.compile(r"(?<![\w.])(plan\.node\d+|kernel\.[A-Za-z_]+)")

# Field numbers of tensorflow/tsl/profiler/protobuf/xplane.proto.
_SPACE_PLANES = 1
_PLANE_NAME = 2
_PLANE_EVENT_METADATA = 4
_PLANE_STAT_METADATA = 5
_META_NAME = 2
_META_STATS = 5
_STAT_METADATA_ID = 1
_STAT_STR = 5
_STAT_REF = 7


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes):
    """``(field number, value)`` of one message: an int for varints, bytes
    for length-delimited and fixed-width fields."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _map_entries(entries) -> dict:
    """A protobuf ``map<int64, Message>``: key 1, value 2."""
    out = {}
    for entry in entries:
        kv = dict(_fields(entry))
        out[kv.get(1, 0)] = kv.get(2, b"")
    return out


def tf_ops(path: str) -> dict[str, dict[str, str]]:
    """For each ``/device:TPU:*`` plane of an ``.xplane.pb``, a map from op
    event name (the name ``ProfileData`` gives the event) to its
    ``tf_op``."""
    with open(path, "rb") as f:
        space = f.read()
    out: dict[str, dict[str, str]] = {}
    for num, plane in _fields(space):
        if num != _SPACE_PLANES:
            continue
        fields = list(_fields(plane))
        name = next((v.decode() for n, v in fields if n == _PLANE_NAME), "")
        if not name.startswith(DEVICE_PLANE_PREFIX):
            continue
        stat_names = {
            k: dict(_fields(v)).get(_META_NAME, b"").decode()
            for k, v in _map_entries(
                v for n, v in fields if n == _PLANE_STAT_METADATA).items()}
        ops: dict[str, str] = {}
        for meta in _map_entries(
                v for n, v in fields if n == _PLANE_EVENT_METADATA).values():
            mf = list(_fields(meta))
            ev_name = next((v.decode() for n, v in mf if n == _META_NAME), "")
            for n, stat in mf:
                if n != _META_STATS:
                    continue
                sf = dict(_fields(stat))
                if stat_names.get(sf.get(_STAT_METADATA_ID)) != TF_OP:
                    continue
                if _STAT_STR in sf:
                    ops[ev_name] = sf[_STAT_STR].decode()
                elif _STAT_REF in sf:
                    ops[ev_name] = stat_names.get(sf[_STAT_REF], "")
        out[name] = ops
    return out


def scope_key(tf_op: str) -> str:
    """``"plan.nodeN/kernel.x"`` from the innermost plan-node and kernel
    scopes of an op's ``tf_op`` path (a transform may wrap one, as in
    ``vmap(plan.node3)``); ``"unscoped"`` with neither."""
    node = kernel = None
    for scope in _SCOPE.findall(tf_op):
        if scope.startswith("plan."):
            node = scope
        else:
            kernel = scope
    return "/".join(p for p in (node, kernel) if p) or UNSCOPED


def reduce_scoped(device_ops: dict, host_spans, window,
                  ops_tf: dict) -> dict:
    """``device_scopes`` and ``idle_by_span`` on plain data, in the shapes
    :func:`bench.harness.trace.reduce_events` takes (``ops_tf`` maps a
    device to its op-name -> ``tf_op`` map)."""
    lo, hi = window
    scopes: dict[str, float] = {}
    idle: dict[str, float] = {}
    for dev, ops in device_ops.items():
        tf = ops_tf.get(dev, {})
        clipped = [(name, max(s, lo), min(e, hi)) for name, s, e in ops
                   if e > lo and s < hi]
        for name, t in self_times(clipped).items():
            key = scope_key(tf.get(name, ""))
            scopes[key] = scopes.get(key, 0.0) + t
        _, merged = union_length([(s, e) for _, s, e in clipped])
        for gap in gaps_between(merged, lo, hi):
            label = label_gap(gap, host_spans)
            idle[label] = idle.get(label, 0.0) + gap[1] - gap[0]
    return {"device_scopes": _longest_first(scopes),
            "idle_by_span": _longest_first(idle)}


def _longest_first(seconds: dict) -> dict:
    return dict(sorted(seconds.items(), key=lambda kv: -kv[1]))


def read_scoped(path: str) -> dict:
    """:func:`reduce_scoped` of one ``.xplane.pb`` over its ``bench.window``
    span (the whole trace where it has none)."""
    device_ops, host_spans, window = read_events(path)
    if window is None:
        times = [t for ops in device_ops.values() for _, s, e in ops
                 for t in (s, e)]
        window = (min(times), max(times))
    return reduce_scoped(device_ops, host_spans, window, tf_ops(path))
