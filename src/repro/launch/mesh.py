"""Production mesh construction.

A function (not a module constant) so importing never touches jax device
state. Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2x16x16 = 512
chips (pod, data, model) — the pod axis carries color-coding iterations /
data parallelism across pods.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh"]


def make_mesh(shape, axes, devices=None):
    """A mesh of Auto axes over ``devices`` (default: all of them)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
