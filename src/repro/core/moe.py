"""The Sparsely-Gated Mixture-of-Experts layer (§2) as a composable module.

``moe_defs`` declares the parameters; ``moe_apply`` runs routing → dispatch →
expert FFN → combine and returns (output, aux) where aux carries the §4
balancing losses and the Table-6 diagnostics.

Expert networks are the paper's one-hidden-layer ReLU FFNs by default;
``activation="swiglu"`` upgrades them to gated-SiLU experts (w1/w3/w2) for
the modern architectures in the zoo (kimi-k2, arctic, jamba).

Routing is configured by a single :class:`repro.core.router.RouterSpec`
(``MoEArgs.router``, docs/routing.md): policy, k, train/eval capacity
factors, noise, balance-loss weights.  ``router.route(params, x,
mask=...)`` returns a typed :class:`~repro.core.router.RouteDecision`; the
legacy ``gating_mode``/``dispatch_impl``/``expert_impl`` strings (and the
old per-carrier ``capacity_factor`` floats) are a deprecated shim that
``router.resolve_spec`` folds into a spec.  ``mask`` marks valid tokens —
the serving engine passes slot occupancy so dead slots neither route nor
consume expert capacity.

The hot-path ops (top-k gating, dispatch/combine, expert FFN) route
through the kernel backend registry (``repro.kernels.backend``,
docs/kernels.md): ``kernel_backend="ref"`` is the jnp/XLA path,
``"pallas"`` the fused trainable kernels.  Resolution is explicit — an
unknown or broken backend raises instead of degrading silently.

Distribution: logical axes are annotated so that under the ``dp_tp_ep`` plan
experts shard over the *model* mesh axis (expert parallelism, §3.1) while
their d_model dimension shards over *data* (FSDP — exactly one copy of every
expert across the cluster, as the paper specifies).  The explicit all-to-all
schedule lives in ``expert_parallel.py``; this module uses GSPMD constraints.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax
import jax.numpy as jnp

from repro.common.param import ParamDef
from repro.core import dispatch as dsp
from repro.core import gating
from repro.core import router as router_lib
from repro.kernels import backend as backend_lib
from repro.sharding import context as ctx_lib


@dataclasses.dataclass(frozen=True)
class MoEArgs:
    n_experts: int
    k: int
    d_model: int
    d_ff: int
    activation: str = "relu"            # relu (paper) | swiglu
    # --- routing ------------------------------------------------------------
    # The one configuration path for gating/dispatch/capacity (docs/
    # routing.md).  None resolves the deprecated string/float fields below
    # into a spec; the spec's k inherits from ``k`` above.
    router: "router_lib.RouterSpec | None" = None
    # Deprecated spellings (router.resolve_spec shim; DeprecationWarning):
    gating_mode: str = "noisy_topk"     # noisy_topk | batchwise | threshold
    capacity_factor: float | None = None   # None = RouterSpec default (2.0)
    # None = same as training.  NOTE: this used to default to 2.0
    # *independently* of capacity_factor, so a legacy caller that set only
    # capacity_factor evaluated at 2.0; it now evaluates at the training
    # factor (set eval_capacity_factor explicitly to pin the old value).
    eval_capacity_factor: float | None = None
    w_importance: float = 0.1           # paper §C.1
    w_load: float = 0.1
    dispatch_impl: str = "sort"         # sort | einsum (ref backend only)
    expert_impl: str = "einsum"         # legacy spelling of kernel_backend
    priority_dispatch: bool = False
    # --- kernels ------------------------------------------------------------
    # Kernel backend for the hot path (see repro/kernels/backend.py):
    # "ref" | "pallas"; None derives from the legacy expert_impl field.
    # Resolution is explicit — an unknown or broken backend raises
    # KernelBackendError instead of silently degrading to the slow path.
    kernel_backend: str | None = None
    # VMEM budget (bytes) for the fused dispatch/combine kernels; None
    # uses kernels.dispatch.DEFAULT_VMEM_LIMIT.  Past the limit the pallas
    # backend E-blocks the buffer (only an [e_block, C, d] slab resident
    # per grid step); only a shape whose one-expert slab still exceeds the
    # budget falls back to the ref scatter.
    dispatch_vmem_limit: int | None = None
    # Expert-block size for the fused dispatch/combine kernels: None
    # auto-selects against the VMEM budget (whole buffer resident when it
    # fits, else the largest fitting power-of-two slab); an explicit int
    # forces that slab size for both forward and backward.
    dispatch_e_block: int | None = None
    # Plan expert-FFN GMM tiles by the tiling table, else the tile rule
    # (docs/kernels.md §Tiling autotune); False pins static 128 tiles.
    gmm_autotune: bool = True
    # Serve-time fused decode: run routing + dispatch + expert FFN +
    # combine as ONE kernel launch (docs/kernels.md §Fused decode step).
    # Inference-only — ignored under train=True; the backend falls back
    # (RuntimeWarning) to the unfused pipeline past the VMEM slab budget.
    # Set by the model layer for decode-shaped calls only.
    fused_decode: bool = False
    sigmoid_output: bool = False        # paper's LM passes MoE out thru sigmoid
    wide_dispatch: bool = True          # §3.1 combined-batch token resharding
    dtype: Any = jnp.bfloat16


def moe_defs(a: MoEArgs) -> dict:
    spec = router_lib.resolve_spec(a)
    gated = a.activation == "swiglu"
    defs = dict(router_lib.Router(spec, a.n_experts).gate_defs(a.d_model))
    defs.update({
        "w1": ParamDef((a.n_experts, a.d_model, a.d_ff),
                       ("experts", "expert_embed", "expert_mlp"),
                       dtype=a.dtype, fan_in=a.d_model),
        "w2": ParamDef((a.n_experts, a.d_ff, a.d_model),
                       ("experts", "expert_mlp", "expert_embed"),
                       dtype=a.dtype, fan_in=a.d_ff),
    })
    if gated:
        defs["w3"] = ParamDef((a.n_experts, a.d_model, a.d_ff),
                              ("experts", "expert_embed", "expert_mlp"),
                              dtype=a.dtype, fan_in=a.d_model)
    return defs


def expert_ffn(params, x: jax.Array, a: MoEArgs,
               ctx: ctx_lib.MeshContext | None = None) -> jax.Array:
    """Apply every expert to its [E, C, d] buffer of dispatched tokens.

    Routed through the kernel backend registry — resolution is explicit
    and raises on an unknown/broken backend (no silent degradation)."""
    return backend_lib.resolve(a).expert_ffn(params, x, a, ctx=ctx)


def run_gating(params, x: jax.Array, a: MoEArgs, *, train: bool,
               rng: jax.Array | None,
               topk_impl=None) -> gating.GatingInfo:
    """Deprecated: use ``router.build(a).route(...)`` (docs/routing.md).

    ``raw_logits`` is reconstructed as log-gates (the batchwise/threshold
    convention) — RouteDecision does not carry the pre-noise logits."""
    warnings.warn("run_gating is deprecated; use repro.core.router "
                  "(build(a).route(...))", DeprecationWarning, stacklevel=2)
    dec = router_lib.build(a, topk_impl=topk_impl).route(
        params, x, train=train, rng=rng)
    return gating.GatingInfo(
        combine_weights=dec.combine_weights,
        expert_index=dec.expert_index, gates=dec.gates, load=dec.load,
        raw_logits=jnp.log(jnp.maximum(dec.gates, 1e-20)))


def moe_apply(params, x: jax.Array, a: MoEArgs, *, train: bool = True,
              rng: jax.Array | None = None,
              ctx: ctx_lib.MeshContext | None = None,
              mask: jax.Array | None = None
              ) -> tuple[jax.Array, dict]:
    """x: [T, d_model] (tokens already flattened — the paper's 'convolutional'
    application over all positions of a batch, §3.1).

    ``ctx`` is the explicit sharding context; ``None`` resolves the
    contextvar (identity constraints off-mesh).  ``mask`` ([T] in {0,1})
    marks valid tokens: masked tokens (dead serving slots, bucketed-
    prefill padding) get zero gate weight, zero load/telemetry, and
    consume no expert capacity."""
    t, d = x.shape
    bk = backend_lib.resolve(a)     # explicit: raises on unknown/broken
    if not train and a.fused_decode and bk.decode_step is not None:
        # One-launch decode step: the backend fuses routing -> scatter ->
        # expert FFN -> combine (the pipeline below, to float tolerance) and
        # emits the same load/overflow telemetry families.  Decode
        # consumers discard losses/metrics, so aux carries zeros.
        token_axis = "tokens" if a.wide_dispatch else "batch"
        y, telemetry = bk.decode_step(params, x, a, mask=mask, ctx=ctx)
        y = ctx_lib.with_constraint(y, (token_axis, "embed"), ctx)
        if a.sigmoid_output:
            y = jax.nn.sigmoid(y.astype(jnp.float32)).astype(x.dtype)
        zero = jnp.zeros((), jnp.float32)
        return y, {"aux_loss": zero,
                   "metrics": {k: zero for k in
                               ("cv_importance", "cv_load",
                                "max_over_mean_load", "fraction_dropped")},
                   "telemetry": telemetry}
    router = router_lib.build(a, topk_impl=bk.topk_impl)
    dec = router.route(params, x, train=train, rng=rng, mask=mask)

    token_axis = "tokens" if a.wide_dispatch else "batch"
    x = ctx_lib.with_constraint(x, (token_axis, "embed"), ctx)
    buf = bk.dispatch(x, dec, a, ctx=ctx)
    buf = ctx_lib.with_constraint(
        buf, ("experts", "expert_capacity", "embed"), ctx)
    out = bk.expert_ffn(params, buf, a, ctx=ctx)
    out = ctx_lib.with_constraint(
        out, ("experts", "expert_capacity", "embed"), ctx)
    y = bk.combine(out, dec, a, dtype=x.dtype, ctx=ctx)
    y = ctx_lib.with_constraint(y, (token_axis, "embed"), ctx)
    if a.sigmoid_output:
        y = jax.nn.sigmoid(y.astype(jnp.float32)).astype(x.dtype)

    return y, {"aux_loss": dec.aux_loss, "metrics": dec.metrics,
               "telemetry": dec.telemetry}


def gating_telemetry(info: gating.GatingInfo, p: dsp.DispatchPlan) -> dict:
    """Back-compat alias for :func:`repro.core.router.route_telemetry`."""
    return router_lib.route_telemetry(info, p)
