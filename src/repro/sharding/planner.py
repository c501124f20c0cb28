"""The sharding planner: walk a param pytree, assign every leaf a
PartitionSpec from the per-family rule table (:mod:`repro.sharding.rules`),
sanitize against a mesh, and compose with the Parle replica axis.

This is the subsystem behind ``--mesh replica:n,data:d,model:m``:

  * FSDP rides the ``data`` axis, tensor parallelism the ``model`` axis —
    both *inside* a replica, so their collectives (weight all-gathers,
    partial-sum reductions) never cross the replica boundary;
  * the ``replica``/``pod`` axis is prepended to optimizer-state specs
    (``("replica", *plan(leaf))``), so the Eq. (8d) sync all-reduce moves
    shard-size bytes per device, once every L steps.

The planner is deliberately transparent: every :class:`LeafPlan` records
which rule fired and which dims the divisibility sanitizer demoted, and
each demotion is logged exactly once per process (no silent replication).

Entry points:
  plan_tree(tree, mesh=None, policy=...)   -> Plan (specs + provenance)
  constrain_tree(tree, mesh, lead=...)     -> with_sharding_constraint'd
      tree for use INSIDE a shard_map body whose in-replica axes are
      ``auto`` (the leading ``lead`` dims — local replica axes — stay
      unconstrained)
  ShardContext                             -> per-leaf specs for the
      Pallas kernels' nested shard_map (kernels/parle_update.py)
"""
from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding import rules

log = logging.getLogger("repro.sharding")

# (path, axis) pairs already warned about — each planner demotion is
# surfaced exactly once per process, not once per trace
_WARNED: set = set()


def path_names(path) -> Tuple[str, ...]:
    """Key path -> name tuple (the ONE place key-path entries are
    stringified; kernels and partition.py reuse it)."""
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "name"):
            out.append(str(p.name))
        else:
            out.append(str(getattr(p, "idx", p)))
    return tuple(out)


def match_rule(names: Sequence[str], shape: Tuple[int, ...]):
    """Walk the rule table; returns (rule_name, spec).  Leaves under a
    layer-stack path ("blocks"/"layers") match on their per-layer shape
    and get a leading None for the scan axis."""
    if any(n in rules.STACK_PATH_NAMES for n in names) and len(shape) >= 1:
        name, spec = match_rule_flat(names, shape[1:])
        return name, P(None, *spec)
    return match_rule_flat(names, shape)


def match_rule_flat(names, shape):
    for rule_name, fn in rules.RULE_TABLE:
        spec = fn(names, shape)
        if spec is not None:
            return rule_name, spec
    raise AssertionError("fallback rule must match")     # pragma: no cover


def _apply_policy(spec: P, policy: str) -> P:
    """Policy transforms over the fsdp_tp base assignment (see
    partition.param_pspecs docstring for the trade-offs)."""
    if policy == "fsdp_tp":
        return spec
    if policy == "tp_only":
        return P(*[None if ax == rules.DATA else ax for ax in spec])
    if policy == "dp_only":
        out, used = [], False
        for ax in spec:
            if ax == rules.DATA and not used:
                out.append((rules.DATA, rules.MODEL))
                used = True
            elif ax in (rules.MODEL, rules.DATA):
                out.append(None)
            else:
                out.append(ax)
        return P(*out)
    raise ValueError(f"unknown sharding policy {policy!r}")


def _sanitize(spec: P, shape, axis_sizes: dict, path_names=(),
              warn: bool = True):
    """Demote mesh axes that do not evenly divide the dim (pjit argument
    shardings must divide exactly).  Returns (spec, demoted_dims)."""
    dims = list(spec) + [None] * (len(shape) - len(spec))
    out, demoted = [], []
    for i, (dim_size, axis) in enumerate(zip(shape, dims)):
        if axis is None:
            out.append(None)
            continue
        names = axis if isinstance(axis, tuple) else (axis,)
        if any(nm not in axis_sizes for nm in names):
            # axis absent from this mesh (e.g. replica-only mesh): not a
            # planner gap, just a smaller mesh — demote silently
            out.append(None)
            demoted.append(i)
            continue
        total = 1
        for nm in names:
            total *= axis_sizes[nm]
        if dim_size % total == 0 and dim_size >= total:
            out.append(axis)
        else:
            out.append(None)
            demoted.append(i)
            if warn:
                key = (tuple(path_names), i, axis)
                if key not in _WARNED:
                    _WARNED.add(key)
                    log.warning(
                        "sharding planner: %s dim %d (size %d) not "
                        "divisible by mesh axis %r (size %d) — demoted "
                        "to replicated",
                        "/".join(path_names) or "<leaf>", i, dim_size,
                        axis, total)
    return P(*out), tuple(demoted)


@dataclass(frozen=True)
class LeafPlan:
    path: Tuple[str, ...]
    shape: Tuple[int, ...]
    rule: str                 # which rules.RULE_TABLE entry fired
    spec: P                   # final (policy-applied, sanitized) spec
    raw_spec: P               # rule output before sanitizing
    demoted: Tuple[int, ...]  # dim indices the sanitizer replicated


@dataclass(frozen=True)
class Plan:
    leaves: Tuple[LeafPlan, ...]
    treedef: Any
    axis_sizes: Optional[dict]      # None = no mesh given (no sanitize)

    def pspecs(self):
        """Per-leaf PartitionSpec tree (same structure as the input)."""
        return jax.tree_util.tree_unflatten(
            self.treedef, [l.spec for l in self.leaves])

    def pspecs_with_leading(self, *axes):
        """Per-leaf specs with leading axes prepended (the Parle replica
        axis composition: ``("replica", *plan(leaf))``)."""
        return jax.tree_util.tree_unflatten(
            self.treedef, [P(*axes, *l.spec) for l in self.leaves])

    def shardings(self, mesh: Mesh):
        return jax.tree_util.tree_unflatten(
            self.treedef,
            [NamedSharding(mesh, l.spec) for l in self.leaves])

    def by_rule(self) -> dict:
        out: dict = {}
        for l in self.leaves:
            out.setdefault(l.rule, []).append("/".join(l.path))
        return out

    def demotions(self) -> list:
        return [l for l in self.leaves if l.demoted]


def plan_tree(tree, mesh: Optional[Mesh] = None, policy: str = "fsdp_tp",
              warn: bool = True) -> Plan:
    """Plan a parameter tree (arrays or ShapeDtypeStructs).

    With a ``mesh``, specs are sanitized against its axis sizes and every
    demotion is logged once; without, raw rule specs are returned
    (callers then sanitize via :func:`repro.sharding.partition.sanitize_pspecs`).
    """
    axis_sizes = dict(mesh.shape) if mesh is not None else None
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = []
    for path, leaf in flat:
        names = path_names(path)
        shape = tuple(leaf.shape)
        rule_name, raw = match_rule(names, shape)
        spec = _apply_policy(raw, policy)
        demoted: Tuple[int, ...] = ()
        if axis_sizes is not None:
            spec, demoted = _sanitize(spec, shape, axis_sizes, names, warn)
        leaves.append(LeafPlan(path=names, shape=shape, rule=rule_name,
                               spec=spec, raw_spec=raw, demoted=demoted))
    return Plan(leaves=tuple(leaves), treedef=treedef, axis_sizes=axis_sizes)


# ------------------------------------------------------------------
# In-body composition: sharding constraints + kernel shard context
# ------------------------------------------------------------------

def in_replica_axes(mesh: Mesh, replica_axis: Optional[str]) -> Tuple[str, ...]:
    """Mesh axes that do real work INSIDE a replica: everything except
    the replica axis, with size > 1."""
    return tuple(a for a in mesh.axis_names
                 if a != replica_axis and mesh.shape[a] > 1)


def constrain_tree(tree, mesh: Mesh, lead: int = 0, policy: str = "fsdp_tp"):
    """``with_sharding_constraint`` every leaf to its planner spec over
    the in-replica (auto) axes.  For use INSIDE a shard_map body whose
    replica axis is manual: the leading ``lead`` dims (the local replica
    axis) stay unconstrained, the trailing dims get the plan of the
    leaf's per-replica shape."""

    def fix(path, leaf):
        names = path_names(path)
        shape = tuple(leaf.shape[lead:])
        _, raw = match_rule(names, shape)
        spec = _apply_policy(raw, policy)
        spec, _ = _sanitize(spec, shape, dict(mesh.shape), names, warn=True)
        full = P(*([None] * lead), *spec)
        # inside a shard_map body a bare spec resolves against the body's
        # context mesh (replica axis Manual); a NamedSharding over
        # ``mesh`` would not match it
        return jax.lax.with_sharding_constraint(
            leaf, full if in_body else NamedSharding(mesh, full))

    in_body = not jax.sharding.get_abstract_mesh().empty
    return jax.tree_util.tree_map_with_path(fix, tree)


@dataclass(frozen=True)
class ShardContext:
    """What the Pallas kernel drivers need to run on LOCAL shards: the
    mesh and, per leaf, the spec of its per-replica (trailing) dims.
    The kernel wraps each leaf's flat update in a nested shard_map over
    the in-replica axes so the block grid covers the local shard only
    (kernels/parle_update.py)."""

    mesh: Mesh
    policy: str = "fsdp_tp"

    def leaf_spec(self, path_names: Sequence[str],
                  shape: Tuple[int, ...]) -> P:
        """Spec of a leaf's per-replica dims (no replica axis)."""
        _, raw = match_rule(tuple(path_names), tuple(shape))
        spec = _apply_policy(raw, self.policy)
        spec, _ = _sanitize(spec, tuple(shape), dict(self.mesh.shape),
                            path_names, warn=False)
        return spec


def make_shard_context(mesh: Optional[Mesh], replica_axis: Optional[str],
                       policy: str = "fsdp_tp") -> Optional[ShardContext]:
    """A ShardContext when the mesh actually has in-replica axes to ride;
    None otherwise (local path / replica-only mesh — kernels then run on
    the whole per-device block exactly as before)."""
    if mesh is None or not in_replica_axes(mesh, replica_axis):
        return None
    return ShardContext(mesh=mesh, policy=policy)
