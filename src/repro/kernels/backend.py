"""Kernel backend registry: the one switch between the jnp reference path
and the Pallas hot path.

The MoE layer's three compute hot-spots — top-k gating (Eqs. 3/5), the
dispatch/combine scatter, and the expert FFN grouped matmul (§3.2: the
experts carry ~40% of total FLOPs) — each exist twice in this repo: a pure
jnp/XLA reference and a fused Pallas kernel.  A :class:`KernelBackend`
bundles one coherent set of the three; ``moe_apply``, the expert-parallel
schedule, the trainer, and the microbenchmarks all go through
:func:`resolve` instead of importing kernels ad hoc.

Resolution is **explicit**: a backend that fails to import registers as
broken and ``get()`` raises :class:`KernelBackendError` with the original
import error — never a silent fall-back to the slow path (the lazy
``from repro.kernels import ops`` in old ``core/moe.py`` would degrade
with no signal; this registry is the fix).  Selection order:
``MoEArgs.kernel_backend`` if set, else the legacy ``expert_impl`` field
("pallas" -> pallas, anything else -> ref).

Observability: every backend call site (dispatch / expert-FFN GMM /
combine / fused decode) runs under a ``jax.named_scope`` of its name
(``kernel.dispatch``, ``kernel.gmm``, ``kernel.combine``,
``kernel.decode_step``, ``kernel.decode_proj``).  A scope is metadata:
it prefixes the ``op_name`` of the HLO ops the call emits, which the
device trace shows, and times nothing; the ops keep their instruction
names (``gmm.N``, ``_dispatch_jit.N``, ...), docs/observability.md.

MeshContext awareness
---------------------
Backends consume the explicit sharding context (ROADMAP open item 3):

* :func:`shard_shape` maps a global logical shape to the per-shard view
  under ``ctx`` — dims shrink by the mesh axes that are both assigned by
  the plan *and* held Manual by an enclosing ``shard_map`` (that is what
  the kernel actually sees inside the expert-parallel body);
* :func:`block_plan` turns the per-shard ``[E_local, C, d] x d_ff`` FFN
  shapes into the Pallas block spec (tile sizes + padded dims) via
  ``gmm.plan_blocks`` — non-tile-aligned C/d_ff pad to tile boundaries
  instead of asserting;
* the pallas backend's ``expert_ffn`` validates its buffer against the
  per-shard expectation and fails loudly on a mesh/shape mismatch.
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import warnings
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import dispatch as dsp
from repro.sharding import context as ctx_lib

log = logging.getLogger(__name__)


class KernelBackendError(RuntimeError):
    """Unknown, broken, or mis-shaped kernel backend — never swallowed."""


# Calls that left the pallas kernels for a slower path, by call site
# ("dispatch", "combine", "decode_step", "decode_proj").  Counted where the
# decision is made — at trace time, once per traced shape — so a run can
# assert that its kernels really ran (chip_smoke.py fails on any).
_FALLBACKS: collections.Counter = collections.Counter()


def fallbacks() -> dict[str, int]:
    """Kernel fallbacks taken in this process so far, by call site."""
    return dict(_FALLBACKS)


def record_fallback(what: str, why: str, to: str) -> None:
    """Count a fallback and say so, in the log and as a RuntimeWarning."""
    _FALLBACKS[what] += 1
    msg = f"pallas {what}: {why}; falling back to {to} for this call"
    log.warning(msg)
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# MeshContext -> per-shard shapes / block specs
# ---------------------------------------------------------------------------

def shard_shape(ctx: "ctx_lib.MeshContext | None", shape, logical_axes
                ) -> tuple:
    """Global logical shape -> the per-shard shape a kernel body sees.

    Only mesh axes that the plan assigns to the logical dim *and* that the
    context holds in Manual mode shrink the dim (an enclosing ``shard_map``
    hands the body local blocks; Auto axes are GSPMD's and the kernel still
    sees the global dim at trace time).  Off-mesh this is the identity.
    """
    if ctx is None or ctx.mesh is None or not ctx.manual_axes:
        return tuple(shape)
    out = []
    for dim, logical in zip(shape, logical_axes):
        denom = 1
        for ax in ctx.rules.lookup(logical):
            if ax not in ctx.mesh.shape or ax not in ctx.manual_axes:
                continue
            size = ctx.mesh.shape[ax]
            if dim % (denom * size) == 0:
                denom *= size
        out.append(dim // denom)
    return tuple(out)


def block_plan(a, capacity: int, ctx: "ctx_lib.MeshContext | None" = None,
               *, dtype=None):
    """Per-shard Pallas block plan for the expert FFN's up-projection GMM:
    ``[E_local, C_local, d] x [E_local, d, f_local]``.

    Planning/introspection view of the same derivation the pallas
    ``expert_ffn`` performs on its (per-shard) operands at trace time:
    given the *global* MoE config + capacity, returns the ``gmm.BlockPlan``
    a shard will run — padded dims show exactly how a non-tile-aligned
    capacity / d_ff will be zero-padded on that shard.
    """
    from repro.kernels import gmm as gmm_lib
    e, c, d = shard_shape(
        ctx, (a.n_experts, capacity, a.d_model),
        ("experts", "expert_capacity", "embed"))
    (f,) = shard_shape(ctx, (a.d_ff,), ("expert_mlp",))
    return gmm_lib.plan_blocks(e, c, d, f, dtype or a.dtype,
                               autotune=getattr(a, "gmm_autotune", True))


def _check_local_buffer(x, a, ctx, backend_name: str):
    """Validate a dispatched [E?, C?, d] buffer against the per-shard view."""
    want_e, _, want_d = shard_shape(
        ctx, (a.n_experts, 1, a.d_model),
        ("experts", "expert_capacity", "embed"))
    if x.ndim != 3 or x.shape[2] != want_d or x.shape[0] % want_e != 0:
        raise KernelBackendError(
            f"backend {backend_name!r}: buffer {x.shape} does not match the "
            f"per-shard expert view [E_local={want_e}, C, d={want_d}] under "
            f"ctx manual axes {sorted(ctx.manual_axes) if ctx else None}")


# ---------------------------------------------------------------------------
# the backend record + registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelBackend:
    """One coherent implementation set for the MoE hot path.

    ``topk_impl`` is ``None`` for the jnp path (gating falls back to
    ``lax.top_k``); otherwise ``(noisy_logits, k, kk) -> (combine [T,k],
    idx [T,k], raw top values [T,kk])`` with the softmax fused.
    """
    name: str
    expert_ffn: Callable     # (params, x, a, *, ctx=None) -> [E, C, d]
    dispatch: Callable       # (x, plan, a, *, ctx=None)   -> [E, C, d]
    combine: Callable        # (buf, plan, a, *, dtype=None, ctx=None) -> [T,d]
    topk_impl: Callable | None = None
    # Single grouped matmul over capacity buffers: (x [E,C,K], w [E,K,N],
    # a, *, ctx=None) -> [E,C,N].  The MoA layer's routed Q/O projections
    # use this directly (one projection each, no FFN activation between).
    gmm: Callable | None = None
    # One-launch serve decode step (inference-only; docs/kernels.md §Fused
    # decode step): (params, x [T,d], a, *, mask=None, ctx=None) ->
    # (y [T,d], telemetry dict with expert_load/overflow [E]).  The fused
    # kernel emits the same counter families route_telemetry does, so the
    # serve telemetry path is unchanged fused vs unfused.
    decode_step: Callable | None = None
    # One-launch routed projection over explicit plans (MoA decode):
    # (x, w [E,K,N], plan_in, plan_out, a, *, dtype=None, ctx=None) ->
    # [T_out, N] — fuses dispatch(plan_in) -> gmm -> combine(plan_out).
    decode_proj: Callable | None = None
    # True when the ops are compiled kernels the SPMD partitioner cannot
    # split (Mosaic): on a multi-device mesh they must run inside a
    # shard_map on per-shard blocks (transformer._moe_schedule).
    needs_shard_map: bool = False


_REGISTRY: dict[str, "KernelBackend | Exception"] = {}


def register(backend: KernelBackend) -> None:
    _REGISTRY[backend.name] = backend


def register_broken(name: str, err: Exception) -> None:
    """Record an import failure so ``get(name)`` re-raises it explicitly."""
    _REGISTRY[name] = err


def available() -> list[str]:
    return sorted(n for n, b in _REGISTRY.items()
                  if isinstance(b, KernelBackend))


def get(name: str) -> KernelBackend:
    entry = _REGISTRY.get(name)
    if entry is None:
        raise KernelBackendError(
            f"unknown kernel backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}")
    if isinstance(entry, Exception):
        raise KernelBackendError(
            f"kernel backend {name!r} failed to import: {entry!r}"
        ) from entry
    return entry


def resolve(a) -> KernelBackend:
    """Backend for a MoEArgs-like config (``kernel_backend`` field, else the
    legacy ``expert_impl`` spelling).  Raises KernelBackendError — the MoE
    layer never silently degrades to a different implementation."""
    name = getattr(a, "kernel_backend", None)
    if name is None:
        legacy = getattr(a, "expert_impl", "einsum")
        if legacy != "einsum":
            import warnings
            warnings.warn(
                f"expert_impl={legacy!r} is a deprecated spelling; set "
                "kernel_backend explicitly (docs/kernels.md)",
                DeprecationWarning, stacklevel=2)
        name = "pallas" if legacy == "pallas" else "ref"
    backend = get(name)
    log.debug("kernel backend resolved: %s", name)
    return backend


# ---------------------------------------------------------------------------
# plan unwrapping + dispatch flavour
# ---------------------------------------------------------------------------

def _as_plan(p) -> dsp.DispatchPlan:
    """Backends accept a router ``RouteDecision`` wherever they accept a
    ``DispatchPlan`` — the typed decision carries the plan."""
    return getattr(p, "plan", p)


def _dispatch_impl(a) -> str:
    """Scatter flavour for the ref backend: the RouterSpec's ``dispatch``
    field when a spec is configured, else the legacy ``dispatch_impl``."""
    spec = getattr(a, "router", None)
    if spec is not None:
        return spec.dispatch
    return getattr(a, "dispatch_impl", "sort")


# ---------------------------------------------------------------------------
# unfused decode-step composition (the ref decode_step, and the pallas
# backend's loud fallback when the fused slab exceeds the VMEM budget)
# ---------------------------------------------------------------------------

def _decode_step_via(bk: "KernelBackend", params, x, a, *, mask=None,
                     ctx=None):
    """Route -> dispatch -> expert FFN -> combine through ``bk``'s ops, in
    exactly ``moe_apply``'s order and constraint placement — the unfused
    semantics the fused kernel must match."""
    from repro.core import router as router_lib
    router = router_lib.build(a, topk_impl=bk.topk_impl)
    dec = router.route(params, x, train=False, rng=None, mask=mask)
    token_axis = "tokens" if getattr(a, "wide_dispatch", True) else "batch"
    x = ctx_lib.with_constraint(x, (token_axis, "embed"), ctx)
    buf = bk.dispatch(x, dec, a, ctx=ctx)
    buf = ctx_lib.with_constraint(
        buf, ("experts", "expert_capacity", "embed"), ctx)
    out = bk.expert_ffn(params, buf, a, ctx=ctx)
    out = ctx_lib.with_constraint(
        out, ("experts", "expert_capacity", "embed"), ctx)
    y = bk.combine(out, dec, a, dtype=x.dtype, ctx=ctx)
    return y, dec.telemetry


def _decode_proj_via(bk: "KernelBackend", x, w, plan_in, plan_out, a, *,
                     dtype=None, ctx=None):
    """dispatch(plan_in) -> gmm -> combine(plan_out) through ``bk``'s ops —
    the MoA routed-projection sequence (core/moa.py ``_routed_q``/
    ``_routed_o``); d_model-shaped buffers get the expert-view constraint
    exactly where those helpers place it."""
    d_model = getattr(a, "d_model", None)
    buf = bk.dispatch(x, plan_in, a, ctx=ctx)
    if buf.shape[-1] == d_model:
        buf = ctx_lib.with_constraint(
            buf, ("experts", "expert_capacity", "embed"), ctx)
    out = bk.gmm(buf, w, a, ctx=ctx)
    if out.shape[-1] == d_model:
        out = ctx_lib.with_constraint(
            out, ("experts", "expert_capacity", "embed"), ctx)
    return bk.combine(out, plan_out, a, dtype=dtype, ctx=ctx)


# ---------------------------------------------------------------------------
# "ref" — the pure jnp/XLA reference path
# ---------------------------------------------------------------------------

def _ref_expert_ffn(params, x, a, *, ctx=None):
    with jax.named_scope("kernel.gmm"):
        w1 = params["w1"].astype(a.dtype)
        w2 = params["w2"].astype(a.dtype)
        h = jnp.einsum("ecd,edf->ecf", x, w1,
                       preferred_element_type=jnp.float32)
        if a.activation == "swiglu":
            g = jnp.einsum("ecd,edf->ecf", x, params["w3"].astype(a.dtype),
                           preferred_element_type=jnp.float32)
            h = jax.nn.silu(h) * g
        else:
            h = jax.nn.relu(h)
        h = h.astype(a.dtype)
        return jnp.einsum("ecf,efd->ecd", h, w2,
                          preferred_element_type=jnp.float32).astype(a.dtype)


def _ref_dispatch(x, p, a, *, ctx=None):
    p = _as_plan(p)
    with jax.named_scope("kernel.dispatch"):
        if _dispatch_impl(a) == "einsum":
            return dsp.dispatch_einsum(x, p)
        return dsp.dispatch(x, p)


def _ref_combine(buf, p, a, *, dtype=None, ctx=None):
    p = _as_plan(p)
    with jax.named_scope("kernel.combine"):
        if _dispatch_impl(a) == "einsum":
            return dsp.combine_einsum(buf, p, dtype=dtype)
        return dsp.combine(buf, p, dtype=dtype)


def _ref_gmm(x, w, a, *, ctx=None):
    with jax.named_scope("kernel.gmm"):
        return jnp.einsum(
            "eck,ekn->ecn", x, w.astype(x.dtype),
            preferred_element_type=jnp.float32).astype(x.dtype)


def _ref_decode_step(params, x, a, *, mask=None, ctx=None):
    with jax.named_scope("kernel.decode_step"):
        return _decode_step_via(get("ref"), params, x, a, mask=mask,
                                ctx=ctx)


def _ref_decode_proj(x, w, plan_in, plan_out, a, *, dtype=None, ctx=None):
    with jax.named_scope("kernel.decode_proj"):
        return _decode_proj_via(get("ref"), x, w, plan_in, plan_out, a,
                                dtype=dtype, ctx=ctx)


register(KernelBackend(name="ref", expert_ffn=_ref_expert_ffn,
                       dispatch=_ref_dispatch, combine=_ref_combine,
                       topk_impl=None, gmm=_ref_gmm,
                       decode_step=_ref_decode_step,
                       decode_proj=_ref_decode_proj))


# ---------------------------------------------------------------------------
# "pallas" — the fused kernel path (registered broken if the import fails)
# ---------------------------------------------------------------------------

def _register_pallas() -> None:
    try:
        from repro.kernels import dispatch as dispatch_lib
        from repro.kernels import ops
    except Exception as err:  # noqa: BLE001 — recorded, re-raised on use
        register_broken("pallas", err)
        log.warning("pallas kernel backend unavailable: %r", err)
        return

    def _plan_e_block(a, n_experts, capacity, d, dtype, n_tokens, what):
        """Fused-kernel buffer-regime planning: ``(use_pallas, e_block)``.

        ``e_block=None`` keeps the whole [E, C, d] buffer VMEM-resident;
        an int runs the E-blocked kernels with that slab size.  The
        selection comes from ``dispatch_lib.select_e_block`` against the
        (configurable) budget, so past ~16 MiB the backend *blocks* the
        expert dimension instead of bailing — only a shape whose
        single-expert slab still exceeds the budget falls back to the ref
        scatter (counted, with a warning).  ``MoEArgs.dispatch_e_block``
        forces a slab size explicitly."""
        forced = getattr(a, "dispatch_e_block", None)
        if forced is not None:
            return True, forced
        limit = getattr(a, "dispatch_vmem_limit", None)
        try:
            return True, dispatch_lib.select_e_block(
                n_experts, capacity, d, dtype, n_tokens=n_tokens,
                limit=limit, op=what)
        except dispatch_lib.DispatchVMEMError as err:
            record_fallback(what, str(err), "the ref scatter")
            return False, None

    def _pallas_expert_ffn(params, x, a, *, ctx=None):
        if ctx is not None:
            _check_local_buffer(x, a, ctx, "pallas")
        # Tile choice: leave bm/bn/bk unset so each GMM plans its own
        # per-shard operand shapes (the operands here ARE the per-shard
        # view — a shard_map body hands local blocks, validated above) —
        # the tuning table first, the tile rule otherwise.
        # `MoEArgs.gmm_autotune=False` pins the static defaults.
        with jax.named_scope("kernel.gmm"):
            return ops.expert_ffn(params, x, activation=a.activation,
                                  autotune=getattr(a, "gmm_autotune", True))

    def _pallas_dispatch(x, p, a, *, ctx=None):
        p = _as_plan(p)
        # p.n_experts is authoritative: the EP schedule dispatches local
        # tokens into *global*-E buffers before its all_to_all exchange —
        # exactly where E-blocking matters most.
        ok, e_block = _plan_e_block(a, p.n_experts, p.capacity,
                                    x.shape[-1], x.dtype, x.shape[0],
                                    "dispatch")
        with jax.named_scope("kernel.dispatch"):
            if not ok:
                return dsp.dispatch(x, p)
            return ops.dispatch(x, p.expert_index, p.position,
                                n_experts=p.n_experts, capacity=p.capacity,
                                vmem_limit=getattr(a, "dispatch_vmem_limit",
                                                   None),
                                e_block=e_block)

    def _pallas_combine(buf, p, a, *, dtype=None, ctx=None):
        p = _as_plan(p)
        # Same token-block term as ops.combine's own guard — both derive
        # from COMBINE_BLOCK_T, so a borderline shape cannot pass this
        # guard and trip (or regime-mismatch) the one a layer down.
        n_tok = min(dispatch_lib.COMBINE_BLOCK_T, p.expert_index.shape[0])
        ok, e_block = _plan_e_block(a, buf.shape[0], buf.shape[1],
                                    buf.shape[2], buf.dtype, n_tok,
                                    "combine")
        with jax.named_scope("kernel.combine"):
            if not ok:
                return dsp.combine(buf, p, dtype=dtype)
            return ops.combine(buf, p.weight, p.expert_index, p.position,
                               out_dtype=dtype or buf.dtype,
                               vmem_limit=getattr(a, "dispatch_vmem_limit",
                                                  None),
                               e_block=e_block)

    def _pallas_topk(noisy, k, kk):
        w, idx, vals = ops.topk_gating_full(noisy, k, extra=kk - k)
        return w, idx[:, :k], vals

    def _fused_budget_ok(a, need: int, what: str) -> bool:
        """Guard the fused decode slab against the VMEM budget.  Everything
        (weights included) is resident for the single grid step, so past
        the limit the call is counted as a fallback, warned about
        (RuntimeWarning — same contract as the dispatch VMEM fallback) and
        runs the unfused pallas pipeline."""
        limit = (getattr(a, "dispatch_vmem_limit", None)
                 or dispatch_lib.DEFAULT_VMEM_LIMIT)
        if need <= limit:
            return True
        record_fallback(
            what, f"fused slab needs ~{need / 1e6:.1f} MB VMEM > limit "
            f"{limit / 1e6:.1f} MB", "the unfused kernel pipeline "
            "(docs/kernels.md §Fused decode step)")
        return False

    def _pallas_decode_step(params, x, a, *, mask=None, ctx=None):
        from repro.core import router as router_lib
        from repro.kernels import fused_decode as fused_lib
        spec = router_lib.resolve_spec(a)
        t, d = x.shape
        e = a.n_experts
        k = min(spec.k, e)
        capacity = spec.capacity(t, e, train=False)
        gated = a.activation == "swiglu"
        wdt = params["w1"].dtype
        with jax.named_scope("kernel.decode_step"):
            if spec.policy == "noisy_topk" and not spec.priority_dispatch:
                # Full fusion: eval routing is the deterministic clean-
                # logit top-k, computed in-kernel alongside everything
                # else; telemetry comes back as kernel outputs.
                need = fused_lib.decode_vmem_bytes(
                    t, d, a.d_ff, e, capacity, x.dtype, wdt, gated=gated)
                if not _fused_budget_ok(a, need, "decode_step"):
                    return _decode_step_via(get("pallas"), params, x, a,
                                            mask=mask, ctx=ctx)
                valid = (jnp.ones((t,), jnp.float32) if mask is None
                         else jnp.asarray(mask, jnp.float32).reshape(-1))
                y, load, overflow = ops.fused_decode_step(
                    x, valid, params["gate"]["wg"], params["w1"],
                    params["w2"], params.get("w3") if gated else None,
                    k=k, capacity=capacity, activation=a.activation,
                    vmem_limit=getattr(a, "dispatch_vmem_limit", None))
                return y, {"expert_load": load, "overflow": overflow}
            # Any other policy (expert_choice's batch-global column top-k,
            # Appendix-F batchwise/threshold, priority dispatch): routing
            # runs outside as plain XLA ops — still zero extra kernel
            # launches — and the plan-mode kernel fuses the rest.
            router = router_lib.build(a, topk_impl=None)
            dec = router.route(params, x, train=False, rng=None, mask=mask)
            p = _as_plan(dec)
            need = fused_lib.routed_vmem_bytes(
                t, d, d, a.d_ff, e, p.capacity, x.dtype, wdt,
                mode="ffn", gated=gated)
            if not _fused_budget_ok(a, need, "decode_step"):
                return _decode_step_via(get("pallas"), params, x, a,
                                        mask=mask, ctx=ctx)
            y = ops.fused_routed_apply(
                x, p, p, params["w1"], params["w2"],
                params.get("w3") if gated else None,
                mode="ffn", activation=a.activation, out_dtype=x.dtype,
                vmem_limit=getattr(a, "dispatch_vmem_limit", None))
            return y, dec.telemetry

    def _pallas_decode_proj(x, w, plan_in, plan_out, a, *, dtype=None,
                            ctx=None):
        from repro.kernels import fused_decode as fused_lib
        p_in = _as_plan(plan_in)
        p_out = _as_plan(plan_out)
        with jax.named_scope("kernel.decode_proj"):
            need = fused_lib.routed_vmem_bytes(
                x.shape[0], x.shape[-1], w.shape[-1], 0, p_in.n_experts,
                p_in.capacity, x.dtype, w.dtype, mode="proj")
            if not _fused_budget_ok(a, need, "decode_proj"):
                return _decode_proj_via(get("pallas"), x, w, p_in, p_out,
                                        a, dtype=dtype, ctx=ctx)
            return ops.fused_routed_apply(
                x, p_in, p_out, w, mode="proj",
                out_dtype=dtype or x.dtype,
                vmem_limit=getattr(a, "dispatch_vmem_limit", None))

    def _pallas_gmm(x, w, a, *, ctx=None):
        with jax.named_scope("kernel.gmm"):
            return ops.gmm(x, w.astype(x.dtype), activation="none",
                           autotune=getattr(a, "gmm_autotune", True))

    register(KernelBackend(name="pallas", expert_ffn=_pallas_expert_ffn,
                           dispatch=_pallas_dispatch,
                           combine=_pallas_combine,
                           topk_impl=_pallas_topk, gmm=_pallas_gmm,
                           decode_step=_pallas_decode_step,
                           decode_proj=_pallas_decode_proj,
                           needs_shard_map=True))


_register_pallas()
