"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS *before* calling it.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the pod axis
carries cross-pod data parallelism (gradient all-reduce crosses pods).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over real local devices (tests / examples)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


def _auto(n: int) -> tuple:
    """Auto axes: the sharding hints (``with_sharding_constraint``) assume
    them, and ``jax.make_mesh`` defaults to Explicit axes."""
    return (jax.sharding.AxisType.Auto,) * n
