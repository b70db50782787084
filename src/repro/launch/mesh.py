"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state -- the dry-run sets XLA_FLAGS before first init.
Every axis is ``Auto``: model code pins layouts with
``sharding.ctx.constrain`` (``with_sharding_constraint``), which only
refers to Auto axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, **kw):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (256 chips/pod) single-pod mesh, or 2 pods = 512 chips.

    Axes: "data" carries FSDP + batch DP (+ EP for MoE), "model" carries TP;
    "pod" (multi-pod) is pure DP with gradient all-reduce across pods.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh over the local device (smoke tests / examples)."""
    return _auto_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
