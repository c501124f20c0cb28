"""The one ``shard_map`` spelling every call site in this repo uses."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, mesh, in_specs, out_specs, check: bool = False,
              auto: frozenset = frozenset()):
    """``jax.shard_map`` with the replication check off by default.

    ``check`` maps to ``check_vma``: every use in this repo wants it off
    (pmean inside a cond is not rep-invariant to the checker).

    ``auto``: mesh axes left to GSPMD *inside* the body (partial-manual
    shard_map) — the in-replica FSDP/TP axes of the planner-sharded
    path.  The body is manual over every other axis of ``mesh``.

    Called while tracing another shard_map's body over the same axes
    (the kernels' per-leaf wrap), the nested map runs on that body's
    context mesh and makes manual only the axes still Auto there.
    """
    ctx = jax.sharding.get_abstract_mesh()
    if (not ctx.empty and ctx.axis_names == mesh.axis_names
            and AxisType.Manual in ctx.axis_types):
        mesh = ctx
    manual = {a for a, t in zip(mesh.axis_names, mesh.axis_types)
              if t == AxisType.Manual}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check,
                         axis_names=frozenset(mesh.axis_names)
                         - set(auto) - manual)
