"""Production mesh construction.

Function (not module-level constant) so importing this module never
touches jax device state.  Single pod: 256 chips as (data=16, model=16).
Multi-pod: 2 pods × 256 chips as (pod=2, data=16, model=16) — the "pod"
axis doubles as the FL client axis in the scale-out federated round
(DESIGN.md §3b).

Every mesh is built here with ``Auto`` axis types.  ``jax.make_mesh``
defaults to ``Explicit`` axes, under which ``with_sharding_constraint``
becomes an assertion and indexing a pod-sharded client stack raises a
``ShardingTypeError``; the scale-out round relies on the compiler
propagating shardings instead.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh"]


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many (host) devices exist — used by tests
    and CPU examples."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
