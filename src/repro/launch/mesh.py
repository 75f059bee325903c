"""Production mesh construction.

Axes:
  * ``pod``   — the slow (DCN / inter-pod) axis; pure data parallelism +
                optimizer-state sharding (latency-tolerant collectives only).
  * ``data``  — intra-pod batch/FSDP axis.
  * ``model`` — tensor/expert-parallel axis (fast ICI ring).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state (dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> Mesh:
    """Mesh with ``Auto`` axes (tests use small shapes on forced host
    devices).  ``jax.make_mesh`` defaults to ``Explicit`` axes, under
    which ``with_sharding_constraint`` refuses the GSPMD-style specs the
    engine and the dry-run cells use."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The data-parallel axes present in this mesh ((pod, data) or (data,))."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, (tuple, list)):
        out = 1
        for n in name:
            out *= axis_size(mesh, n)
        return out
    if name is None:
        return 1
    return mesh.shape[name] if name in mesh.axis_names else 1


def filter_spec(mesh: Mesh, *entries) -> PartitionSpec:
    """PartitionSpec dropping axes that are absent from ``mesh``.

    Entries may be None, a name, or a tuple of names; absent names are
    removed (e.g. ``("pod", "data")`` -> ``("data",)`` on a single pod).
    """
    out = []
    for e in entries:
        if e is None:
            out.append(None)
        elif isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in mesh.axis_names)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            out.append(e if e in mesh.axis_names else None)
    return PartitionSpec(*out)


def named(mesh: Mesh, spec: PartitionSpec) -> NamedSharding:
    return NamedSharding(mesh, spec)
