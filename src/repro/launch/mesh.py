"""Mesh factories.

``make_production_mesh`` is the deliverable contract:
  single-pod : (16, 16)      axes ("data", "model")       — 256 chips
  multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

Parle replicas ride the "pod" axis in multi-pod mode (n = 2 there): the
single cross-replica all-reduce of Eq. (8d) is the only traffic crossing
the pod boundary, once every L = 25 steps.  ``make_parle_mesh`` factors a
"replica" axis out of the data axis for single-pod Parle (n x d = 16).

Functions, not module constants — importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: sharding constraints keep their
    GSPMD meaning (Explicit axes, the default, turn them into asserts)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_parle_mesh(n_replicas: int, model_parallel: int = 16,
                    num_devices: int | None = None):
    """Single-pod Parle mesh: ("replica", "data", "model")."""
    nd = num_devices or len(jax.devices())
    assert nd % (n_replicas * model_parallel) == 0, (nd, n_replicas, model_parallel)
    data = nd // (n_replicas * model_parallel)
    return _auto_mesh((n_replicas, data, model_parallel),
                      ("replica", "data", "model"))


def make_host_mesh():
    """Degenerate mesh over whatever devices exist (CPU tests)."""
    nd = len(jax.devices())
    return _auto_mesh((nd, 1), ("data", "model"))


def replica_axis_of(mesh: Mesh) -> str | None:
    for name in ("pod", "replica"):
        if name in mesh.shape:
            return name
    return None


def parse_mesh_spec(spec: str) -> dict[str, int]:
    """Parse a ``--mesh`` flag: "replica:4" / "replica:2,data:4".

    Axis order in the string is the mesh axis order (outermost first).
    """
    out: dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, size = part.partition(":")
        if not size:
            raise ValueError(f"mesh axis {part!r} needs a size: 'name:n'")
        if int(size) < 1:
            raise ValueError(f"mesh axis {part!r} needs a positive size")
        out[name.strip()] = int(size)
    if not out:
        raise ValueError(f"empty mesh spec {spec!r}")
    return out


def make_mesh_from_spec(spec: str) -> Mesh:
    """Build a mesh from a ``--mesh`` string.

    "replica:n" is the Parle layout: one all-reduce over "replica" every
    L steps is the ONLY collective.  Exactly prod(sizes) devices are
    used (the first ones) — leftover devices are left idle rather than
    silently absorbed into an axis nothing shards over.
    """
    axes = parse_mesh_spec(spec)
    devices = jax.devices()
    need = int(np.prod(list(axes.values())))
    if need > len(devices):
        raise ValueError(f"mesh {spec!r} needs {need} devices, have "
                         f"{len(devices)} (hint: XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={need})")
    return Mesh(np.asarray(devices[:need]).reshape(tuple(axes.values())),
                tuple(axes), axis_types=(AxisType.Auto,) * len(axes))
