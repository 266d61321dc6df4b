"""MeshContext: the explicit sharding context threaded through the program.

The paper's §3.1 scheme — data-parallel standard layers, model-parallel
experts, combined-batch all-to-all — only composes when every layer agrees
on which mesh it runs under and which of that mesh's axes an enclosing
``shard_map`` already holds in Manual mode.  Following GShard's discipline,
that agreement is *explicit*: a :class:`MeshContext` bundles

* ``mesh``         — the concrete device mesh (or ``None`` off-mesh: the
                     single-host smoke-test / eager path, where every
                     constraint is a no-op),
* ``rules``        — the active :class:`~repro.sharding.partition
                     .ShardingRules` plan (logical axis → mesh axes),
* ``manual_axes``  — mesh axes an enclosing ``shard_map`` holds in Manual
                     mode.  Constraints emitted inside the body strip these
                     axes: only the Auto axes are GSPMD's to place.  The
                     pipeline constructs this at its ``shard_map`` boundary
                     via :meth:`MeshContext.manual` — no runtime reflection.

and is passed down the layer stack as an ordinary argument.  A thin
contextvar (:func:`current_ctx` / ``with ctx:``) covers entry points that
jit a closure and cannot add a traced argument (the serve engine, the test
harness); it is set at the jit/shard_map boundary, read at trace time, and
never mutated inside traced code.

JAX API surface
---------------
All direct use of the JAX sharding API surface that has moved between
releases lives here (enforced by tests/test_version_compat.py), written
for the installed JAX (0.9): :func:`abstract_mesh_or_none`,
:func:`make_mesh` (Auto axis types), :func:`use_mesh`, :func:`shard_map`
(``axis_names=`` / ``check_vma=``), :func:`axis_size` and
:func:`compiled_cost_analysis`.  Callers use these names, so the next API
move is a one-file change.
"""
from __future__ import annotations

import contextvars
import dataclasses
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common import param as pm
from repro.sharding import partition


# ---------------------------------------------------------------------------
# JAX API wrappers (the ONLY place the repo touches these symbols)
# ---------------------------------------------------------------------------

def abstract_mesh_or_none():
    """The ambient abstract mesh under jit (``jax.set_mesh``), or ``None``
    when no mesh is set; callers then fall back to the explicit context."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis Auto (GSPMD places what the
    explicit constraints leave open)."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(names))


def compiled_cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a flat dict."""
    return dict(compiled.cost_analysis())


def axis_size(axis_name: str) -> int:
    """Size of a named mapped axis inside shard_map."""
    return jax.lax.axis_size(axis_name)


def use_mesh(mesh: Mesh):
    """``jax.set_mesh(mesh)``: the ambient mesh for the enclosed code."""
    return jax.set_mesh(mesh)


# Constraints inside a partially-manual shard_map body are supported by
# the installed JAX: with_constraint strips the Manual axes and constrains
# the Auto ones (kept as a name for callers that branch on it).
CAN_CONSTRAIN_UNDER_MANUAL = True


def shard_map(f, mesh: Mesh, in_specs, out_specs, *,
              manual_axes: Sequence[str] | None = None):
    """``jax.shard_map`` without replication checks.

    ``manual_axes=None`` means fully manual (every mesh axis).  Otherwise
    only the named axes are manual and the rest stay Auto for GSPMD.
    """
    kw = {} if manual_axes is None else {"axis_names": set(manual_axes)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kw)


# ---------------------------------------------------------------------------
# MeshContext
# ---------------------------------------------------------------------------

_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "repro_mesh_context", default=None)


def _strip(spec: P, manual: frozenset) -> P:
    """Drop manual mesh axes from a resolved spec (the stage-axis strip)."""
    if not manual:
        return spec

    def one(entry):
        if entry is None:
            return None
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in axes if a not in manual)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]

    return P(*(one(e) for e in spec))


@dataclasses.dataclass(frozen=True)
class MeshContext:
    """mesh + sharding plan + Manual-mode axes of an enclosing shard_map."""

    mesh: Mesh | None
    rules: partition.ShardingRules
    manual_axes: frozenset = frozenset()

    # -- construction -----------------------------------------------------
    @classmethod
    def for_mesh(cls, mesh: Mesh, plan="dp_tp_ep") -> "MeshContext":
        """Context for a concrete mesh; ``plan`` is a PLANS name or rules."""
        return cls(mesh=mesh, rules=_as_rules(plan))

    @classmethod
    def null(cls, plan="dp_tp_ep") -> "MeshContext":
        """Off-mesh context: every constraint is the identity."""
        return cls(mesh=None, rules=_as_rules(plan))

    def with_plan(self, plan) -> "MeshContext":
        return dataclasses.replace(self, rules=_as_rules(plan))

    def manual(self, *axes: str) -> "MeshContext":
        """Derived context for a shard_map body manual over ``axes``."""
        return dataclasses.replace(
            self, manual_axes=self.manual_axes | frozenset(axes))

    # -- resolution -------------------------------------------------------
    @property
    def auto_axes(self) -> tuple[str, ...]:
        if self.mesh is None:
            return ()
        return tuple(a for a in self.mesh.axis_names
                     if a not in self.manual_axes)

    def resolve(self, shape, logical_axes, fallbacks: list | None = None
                ) -> P:
        """Logical axes -> PartitionSpec (manual axes stripped)."""
        if self.mesh is None:
            raise RuntimeError("resolve() needs a concrete mesh")
        spec = partition.resolve_spec(self.rules, self.mesh, shape,
                                      logical_axes, fallbacks)
        return _strip(spec, self.manual_axes)

    def shd(self, shape, logical_axes, fallbacks: list | None = None
            ) -> NamedSharding:
        return NamedSharding(self.mesh,
                             self.resolve(shape, logical_axes, fallbacks))

    def tree_shardings(self, def_tree, fallbacks: list | None = None):
        """NamedSharding tree for a ParamDef tree.

        (For bare PartitionSpec trees — shard_map in_specs — use
        ``partition.tree_pspecs`` with ``ctx.rules``/``ctx.mesh``.)"""
        def one(d: pm.ParamDef):
            return self.shd(d.shape, d.axes, fallbacks)
        return jax.tree_util.tree_map(one, def_tree, is_leaf=pm.is_def)

    # -- resharding -------------------------------------------------------
    def reshard(self, tree, def_tree, fallbacks: list | None = None):
        """Explicitly relayout a materialized tree onto THIS context's plan.

        The plan-boundary primitive (e.g. the serving prefill_tp →
        decode_std handoff): every leaf is ``device_put`` against the
        sharding this context resolves for the matching ``ParamDef`` —
        an eager, observable cross-plan move rather than whatever layout
        the producing jit happened to leave the arrays in.  Off-mesh this
        is the identity.
        """
        if self.mesh is None:
            return tree
        return jax.device_put(tree, self.tree_shardings(def_tree, fallbacks))

    # -- constraints ------------------------------------------------------
    def with_constraint(self, x, logical_axes):
        """Apply a logical sharding constraint inside jit (no-op off-mesh).

        Off-mesh (``mesh is None`` and no ambient abstract mesh) this is the
        identity — the single-device smoke-test path.  Under a Manual-mode
        enclosing shard_map the Manual axes are stripped and only the Auto
        axes are constrained.
        """
        mesh = self.mesh
        if mesh is None:
            mesh = abstract_mesh_or_none()
            if mesh is None:
                return x
        spec = _strip(
            partition.resolve_spec(self.rules, mesh, x.shape, logical_axes),
            self.manual_axes)
        if all(e is None for e in spec):
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))

    # -- contextvar plumbing ---------------------------------------------
    def __enter__(self) -> "MeshContext":
        tokens = getattr(self, "_tokens", None)
        if tokens is None:
            tokens = []
            object.__setattr__(self, "_tokens", tokens)
        tokens.append(_CTX.set(self))
        return self

    def __exit__(self, *exc):
        _CTX.reset(getattr(self, "_tokens").pop())
        return False


def _as_rules(plan) -> partition.ShardingRules:
    if isinstance(plan, str):
        return partition.PLANS[plan]
    return plan


def current_ctx() -> MeshContext | None:
    """The innermost active context (``with ctx:``), or ``None``."""
    return _CTX.get()


def with_constraint(x, logical_axes, ctx: MeshContext | None = None):
    """Explicit-first constraint: use ``ctx`` if given, else the contextvar,
    else the ambient abstract mesh, else identity."""
    ctx = ctx or current_ctx()
    if ctx is None:
        mesh = abstract_mesh_or_none()
        if mesh is None:
            return x
        ctx = MeshContext(mesh=mesh, rules=partition.PLANS["dp_tp_ep"])
    return ctx.with_constraint(x, logical_axes)
