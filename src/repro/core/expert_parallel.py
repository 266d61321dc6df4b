"""Explicit expert-parallel MoE with the paper's §3.1 communication schedule.

The paper: "We distribute the standard layers ... according to conventional
data-parallel schemes, but keep only one shared copy of each expert.  Each
expert receives a combined batch consisting of the relevant examples from all
of the data-parallel input batches."

TPU mapping (shard_map, explicit collectives):

* tokens shard over the dp axes; gating runs locally (data-parallel, tiny
  replicated gate weights — "the number of gating parameters is small", §3.2);
* each shard dispatches its local tokens into per-expert buffers, then an
  ``all_to_all`` over the *ep* axis exchanges expert-major buffers so every
  shard holds the combined batch for its local experts — the d× expert batch
  improvement of §3.1;
* expert weights shard over the ep axis (expert parallelism) and their
  d_model dim over the dp axis (FSDP: all-gathered on use, reduce-scattered
  in backward) — so exactly **one** copy of every expert exists cluster-wide,
  as in the paper;
* a second ``all_to_all`` returns expert outputs, combined locally.

This is the schedule the GSPMD path must be compared against in §Perf: a2a
moves ``2 * k * tokens * d_model`` bytes per layer, independent of E.

:func:`moe_apply_expert_sharded` is the serving counterpart: every device
sees the whole token batch (so routing is the one global decision a
single device would make), runs its own experts' share of the capacity
buffer — experts split over the expert axis, expert width over the
within-expert axis, exactly as the weights are stored — and one psum sums
the experts' contributions.  No all-to-all, no weight movement.

Both schedules exist because a compiled Pallas TPU kernel is opaque to
the SPMD partitioner: on a multi-device mesh it must run inside a
shard_map, on per-shard blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import losses
from repro.core import router as router_lib
from repro.core.moe import MoEArgs, moe_defs
from repro.kernels import backend as backend_lib
from repro.sharding import context as ctx_lib
from repro.sharding import partition


def _local_moe(params, x_local, mask_local, a: MoEArgs, *, train, rng,
               ep_axis: str, fsdp_axis: str | None, ep: int,
               bk: backend_lib.KernelBackend,
               router: router_lib.Router,
               body_ctx: ctx_lib.MeshContext | None):
    """Body executed per shard under shard_map.

    ``ep`` is the ep-axis size, read from the mesh at the shard_map
    boundary (a static Python int, so the buffer reshapes stay static).
    ``bk`` is the resolved kernel backend; ``router`` the resolved Router
    (routing runs locally on each shard's tokens — data-parallel gating,
    §3.2); ``body_ctx`` the Manual-mode context the backend ops use to
    derive per-shard block specs."""
    ep_rank = jax.lax.axis_index(ep_axis)
    t_local, d = x_local.shape
    if a.n_experts % ep != 0:
        raise ValueError(
            f"n_experts={a.n_experts} must divide over ep={ep} shards")
    e_local = a.n_experts // ep

    # Per-shard rng so noise differs across shards.
    if rng is not None:
        rng = jax.random.fold_in(rng, ep_rank)
        if fsdp_axis is not None:
            rng = jax.random.fold_in(rng, jax.lax.axis_index(fsdp_axis))

    dec = router.route(params, x_local, train=train, rng=rng,
                       mask=mask_local)
    info, p = dec, dec.plan
    capacity = p.capacity
    # Local tokens scatter into a *global*-E buffer before the exchange —
    # the shape where the pallas backend's VMEM planning matters most: past
    # the budget it runs the E-blocked kernels ([e_block, C, d] slabs,
    # a.dispatch_e_block / a.dispatch_vmem_limit) rather than bailing to
    # the ref scatter.
    buf = bk.dispatch(x_local, p, a)                   # [E, C, d] local

    # all_to_all #1: expert-major exchange.  [E, C, d] -> [E/ep, ep*C, d]
    buf = buf.reshape(ep, e_local, capacity, d)
    buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                             tiled=False)              # [ep, e_local, C, d]
    buf = jnp.moveaxis(buf, 0, 1).reshape(e_local, ep * capacity, d)

    # FSDP: all-gather the d_model-sharded expert weights on use.
    def gather_w(w, dim):
        if fsdp_axis is None:
            return w
        return jax.lax.all_gather(w, fsdp_axis, axis=dim, tiled=True)

    w_local = {"w1": gather_w(params["w1"], 1),        # [e_local, d, f]
               "w2": gather_w(params["w2"], 2)}        # [e_local, f, d]
    if a.activation == "swiglu":
        w_local["w3"] = gather_w(params["w3"], 1)
    # The combined batch for the local experts, through the kernel backend:
    # the ops see the per-shard [e_local, ep*C, d] view and derive their
    # block specs from it via body_ctx.
    out = bk.expert_ffn(w_local, buf, a, ctx=body_ctx)

    # all_to_all #2: return to token-major shards.
    out = out.reshape(e_local, ep, capacity, d)
    out = jnp.moveaxis(out, 1, 0)                      # [ep, e_local, C, d]
    out = jax.lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0,
                             tiled=False)
    out = out.reshape(a.n_experts, capacity, d)

    y = bk.combine(out, p, a, dtype=x_local.dtype)
    # Balance statistics are over the *global* batch: psum the raw vectors.
    axes = (ep_axis,) if fsdp_axis is None else (ep_axis, fsdp_axis)
    imp = jax.lax.psum(losses.importance(info.gates), axes)
    load = jax.lax.psum(info.load, axes)
    # Combined-batch balancing losses (paper §3.1/§4: every expert serves
    # one combined batch, so Importance(X)/Load(X) in Eqs. (6)/(11) sum
    # over *all* data-parallel shards).  The router computed shard-local
    # losses; re-derive CV² from the psum'd global vectors and keep only
    # the policy's extra term (e.g. Appendix-F threshold alignment) from
    # the local value — pmean of per-shard CVs is NOT the global CV (each
    # shard routing all its tokens to a different single expert is
    # maximally skewed locally yet perfectly balanced globally; for
    # expert_choice the shard-local load is capacity-uniform by
    # construction, so only the global view can see imbalance at all).
    spec = router.spec
    local_balance = (losses.importance_loss(info.gates, spec.w_importance)
                     + losses.load_loss(info.load, spec.w_load))
    extra = dec.aux_loss - local_balance      # exact: same fp recompute
    aux_loss = (spec.w_importance * losses.cv_squared(imp)
                + spec.w_load * losses.cv_squared(load)
                + jax.lax.pmean(extra, axes))
    metrics = {
        "cv_importance": jnp.sqrt(losses.cv_squared(imp)),
        "cv_load": jnp.sqrt(losses.cv_squared(load)),
        "max_over_mean_load": jnp.max(load) / jnp.maximum(jnp.mean(load),
                                                          1e-9),
        "fraction_dropped": jax.lax.pmean(p.fraction_dropped, axes),
    }
    return y, {"aux_loss": aux_loss, "metrics": metrics}


def moe_apply_ep(params, x, a: MoEArgs, mesh: Mesh | None = None, *,
                 train: bool = True, rng: jax.Array | None = None,
                 ep_axis: str = "model",
                 dp_axes: tuple[str, ...] = ("data",),
                 mask: jax.Array | None = None,
                 ctx: ctx_lib.MeshContext | None = None):
    """Expert-parallel MoE over a flat token batch x: [T, d_model].

    Tokens shard over (dp_axes..., ep_axis); expert weights shard as
    [experts -> ep_axis, d_model -> dp_axes[-1] (FSDP)]; gates replicated.
    ``mask`` ([T] in {0,1}, sharded like the tokens) is the router's
    token-validity mask: masked tokens (dead serving slots, padding)
    route nowhere, consume no capacity, and drop out of the globally
    psum'd importance/load balance statistics.
    The mesh comes from ``ctx`` when given (explicit-first), else the
    positional ``mesh`` argument.  NOTE: only ``ctx.mesh`` is consumed —
    this schedule's sharding is fixed by ``ep_axis``/``dp_axes``, not by
    ``ctx.rules``, and it must own the whole mesh (no enclosing Manual
    axes).
    """
    if ctx is not None and ctx.mesh is not None:
        if ctx.manual_axes:
            raise RuntimeError(
                "moe_apply_ep opens its own shard_map; it cannot run "
                "inside a Manual-mode context")
        mesh = ctx.mesh
    if mesh is None:
        raise RuntimeError(
            "moe_apply_ep needs a mesh (ctx or positional)")
    bk = backend_lib.resolve(a)     # explicit: raises on unknown/broken
    router = router_lib.build(a, topk_impl=bk.topk_impl)
    # Context for the shard_map body: it is manual over every mesh axis,
    # so backend ops derive per-shard [E/ep, C, d] block specs from it.
    # Only meaningful when the plan's expert axis is the ep axis we use.
    body_ctx = (ctx or ctx_lib.MeshContext.for_mesh(mesh)).manual(
        *mesh.axis_names)
    if ep_axis not in body_ctx.rules.lookup("experts"):
        body_ctx = None
    fsdp_axis = dp_axes[-1] if dp_axes else None
    token_spec = P(tuple(dp_axes) + (ep_axis,), None)
    w_specs = {
        "gate": jax.tree_util.tree_map(lambda _: P(None, None),
                                       params["gate"]),
        "w1": P(ep_axis, fsdp_axis, None),
        "w2": P(ep_axis, None, fsdp_axis),
    }
    if "w3" in params:
        w_specs["w3"] = P(ep_axis, fsdp_axis, None)
    if "thresholds" in params:      # Appendix-F policy params: replicated
        w_specs["thresholds"] = jax.tree_util.tree_map(
            lambda _: P(None), params["thresholds"])
    aux_spec = {"aux_loss": P(), "metrics": {
        "cv_importance": P(), "cv_load": P(), "max_over_mean_load": P(),
        "fraction_dropped": P()}}
    fn = functools.partial(_local_moe, a=a, train=train, rng=rng,
                           ep_axis=ep_axis, fsdp_axis=fsdp_axis,
                           ep=mesh.shape[ep_axis], bk=bk, router=router,
                           body_ctx=body_ctx)
    if mask is None:
        return ctx_lib.shard_map(
            lambda p, t: fn(p, t, None), mesh, (w_specs, token_spec),
            (token_spec, aux_spec))(params, x)
    mask_spec = P(tuple(dp_axes) + (ep_axis,))
    return ctx_lib.shard_map(fn, mesh,
                             (w_specs, token_spec, mask_spec),
                             (token_spec, aux_spec))(params, x, mask)


def _axes_of(spec_entry) -> tuple[str, ...]:
    if spec_entry is None:
        return ()
    return spec_entry if isinstance(spec_entry, tuple) else (spec_entry,)


def moe_apply_expert_sharded(params, x, a: MoEArgs, *,
                             ctx: ctx_lib.MeshContext,
                             mask: jax.Array | None = None):
    """Inference MoE over a flat token batch x: [T, d_model] on ``ctx``'s
    mesh, with the expert weights where the plan stores them.

    Every device routes the whole batch (the same decision one device
    would make: capacity, drops and telemetry are global), keeps the
    assignments to its own experts (expert axis rank) and runs them
    through the backend's dispatch -> expert FFN -> combine on its shard
    of the expert width; the f32 partial outputs are summed with one psum
    over the expert and width axes.  Returns ``(y, aux)`` like
    :func:`repro.core.moe.moe_apply` with ``train=False``.
    """
    mesh = ctx.mesh
    if mesh is None or ctx.manual_axes:
        raise RuntimeError(
            "moe_apply_expert_sharded needs a concrete mesh and opens its "
            "own shard_map (no enclosing Manual axes)")
    bk = backend_lib.resolve(a)
    if a.fused_decode:
        backend_lib.record_fallback(
            "decode_step", "experts are sharded over the mesh",
            "the unfused kernel pipeline")
    router = router_lib.build(a, topk_impl=bk.topk_impl)
    defs = moe_defs(a)
    w_specs = {n: partition.resolve_spec(ctx.rules, mesh, defs[n].shape,
                                         defs[n].axes)
               for n in ("w1", "w2", "w3") if n in params}
    ep_axes = _axes_of(w_specs["w1"][0])
    width_axes = _axes_of(w_specs["w1"][2])
    if _axes_of(w_specs["w2"][1]) != width_axes or (
            "w3" in w_specs and w_specs["w3"] != w_specs["w1"]):
        raise RuntimeError(f"inconsistent expert weight specs {w_specs}")
    ep = 1
    for ax in ep_axes:
        ep *= mesh.shape[ax]
    e_local = a.n_experts // ep
    body_ctx = ctx.manual(*mesh.axis_names)
    sum_axes = ep_axes + width_axes
    token_axis = "tokens" if a.wide_dispatch else "batch"
    x = ctx_lib.with_constraint(x, (token_axis, "embed"), ctx)

    def body(p, x_all, m):
        dec = router.route(p, x_all, train=False, rng=None, mask=m)
        plan = dec.plan
        lo = jax.lax.axis_index(ep_axes) * e_local if ep_axes else 0
        mine = ((plan.expert_index >= lo)
                & (plan.expert_index < lo + e_local))
        local = plan._replace(
            expert_index=jnp.where(mine, plan.expert_index - lo, 0),
            position=jnp.where(mine, plan.position, plan.capacity),
            n_experts=e_local)
        buf = bk.dispatch(x_all, local, a, ctx=body_ctx)
        out = bk.expert_ffn(p, buf, a, ctx=body_ctx)
        y = bk.combine(out, local, a, dtype=jnp.float32, ctx=body_ctx)
        if sum_axes:
            y = jax.lax.psum(y, sum_axes)
        return y.astype(x_all.dtype), {"aux_loss": dec.aux_loss,
                                       "metrics": dec.metrics,
                                       "telemetry": dec.telemetry}

    p_specs = {"gate": jax.tree_util.tree_map(lambda _: P(),
                                              params["gate"]),
               **w_specs}
    if "thresholds" in params:      # Appendix-F policy params: replicated
        p_specs["thresholds"] = jax.tree_util.tree_map(
            lambda _: P(), params["thresholds"])
    params = {n: params[n] for n in p_specs}
    if mask is None:
        mask = jnp.ones((x.shape[0],), jnp.float32)
    # Every output is the same on every device: the routing is global and
    # y is psum'd, so P() (replicated) covers the whole tree.
    y, aux = ctx_lib.shard_map(body, mesh, (p_specs, P(), P()),
                               (P(), P()))(params, x, mask)
    y = ctx_lib.with_constraint(y, (token_axis, "embed"), ctx)
    return y, aux
