"""Two-level hierarchical Mixture-of-Experts (Appendix B).

A primary gating network selects among ``a`` groups; each group is itself a
secondary MoE over ``b`` experts.  Output (Eq. 12):

    y_H = sum_i sum_j G_primary(x)_i * G_i(x)_j * E_{i,j}(x)

Utilization metrics follow Eqs. (13)-(14):

    Importance_H(X)_{i,j} = sum_x Gp(x)_i * G_i(x)_j
    Load_H(X)_{i,j}       = Load_primary(X)_i * Load_i(X^(i))_j / |X^(i)|

The paper used the hierarchy so 16 GPUs could host 4096+ experts with a
small branching factor; here the primary branch maps onto the *model* mesh
axis (one group of secondary experts per model-shard), the exact analogue of
"each secondary MoE resides on one device" (§3.1).

Routing at both levels goes through the Router API (``HMoEArgs.router``
holds one :class:`repro.core.router.RouterSpec`; per-level k comes from
``k_primary``/``k_secondary``): the primary level capacity-dispatches
tokens into [a, Cp, d] buffers, then the secondary routers run vmapped
over groups with the dispatch-padding slots passed as the router's
token-validity ``mask`` — padded (zero) tokens influence neither gates
nor load statistics.  ``noisy_topk`` and ``expert_choice`` policies are
supported; the Appendix-F batchwise/threshold policies need per-level
threshold parameters the hierarchy does not declare and raise RouterError.

Both levels route their hot-path ops (dispatch/combine scatter, expert
FFN) through the kernel backend registry (``repro.kernels.backend``) —
``kernel_backend="pallas"`` runs the fused kernels (vmapped over groups at
the secondary level), ``"ref"`` the jnp path; resolution is explicit and
raises on an unknown/broken backend, same as the flat MoE layer.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.common.param import ParamDef
from repro.core import dispatch as dsp
from repro.core import gating, losses
from repro.core import router as router_lib
from repro.kernels import backend as backend_lib
from repro.sharding import context as ctx_lib


@dataclasses.dataclass(frozen=True)
class HMoEArgs:
    n_groups: int                 # a — primary branching factor
    n_experts_per_group: int      # b — secondary branching factor
    k_primary: int                # paper: k=2 at each level for the big LMs
    k_secondary: int
    d_model: int
    d_ff: int
    activation: str = "relu"
    # --- routing (docs/routing.md) ------------------------------------------
    # One spec for both levels; k is overridden per level.  None resolves
    # the deprecated fields below via router.resolve_spec.
    router: "router_lib.RouterSpec | None" = None
    capacity_factor: float | None = None   # deprecated; None = spec default
    w_importance: float = 0.1
    w_load: float = 0.1
    dispatch_impl: str = "sort"         # deprecated; ref backend: sort|einsum
    # --- kernels ------------------------------------------------------------
    # Kernel backend (repro/kernels/backend.py): "ref" | "pallas"; None
    # resolves to "ref" (explicit resolution — unknown/broken raises).
    kernel_backend: str | None = None
    dispatch_vmem_limit: int | None = None
    dispatch_e_block: int | None = None    # fused-kernel slab size; None=auto
    gmm_autotune: bool = True              # GMM tile rule (kernels.md)
    dtype: Any = jnp.bfloat16

    @property
    def n_experts(self) -> int:
        return self.n_groups * self.n_experts_per_group


_HMOE_POLICIES = ("noisy_topk", "expert_choice")


def _level_specs(a: HMoEArgs):
    """(primary, secondary) RouterSpecs from the carrier's single spec."""
    spec = router_lib.resolve_spec(a)
    if spec.policy not in _HMOE_POLICIES:
        raise router_lib.RouterError(
            f"hierarchical MoE supports policies {_HMOE_POLICIES}, got "
            f"{spec.policy!r} (Appendix-F modes need per-level threshold "
            "parameters the hierarchy does not declare)")
    return spec.replace(k=a.k_primary), spec.replace(k=a.k_secondary)


def hmoe_defs(a: HMoEArgs) -> dict:
    gated = a.activation == "swiglu"
    _level_specs(a)                 # validate the policy early
    defs = {
        "gate_primary": gating.gating_defs(a.d_model, a.n_groups),
        # Secondary gates stacked over groups: [a, d_model, b].
        "gate_secondary": {
            "wg": ParamDef((a.n_groups, a.d_model, a.n_experts_per_group),
                           ("expert_groups", "embed", "experts"),
                           init="zeros", dtype=jnp.float32),
            "wnoise": ParamDef((a.n_groups, a.d_model,
                                a.n_experts_per_group),
                               ("expert_groups", "embed", "experts"),
                               init="zeros", dtype=jnp.float32),
        },
        "w1": ParamDef((a.n_groups, a.n_experts_per_group, a.d_model, a.d_ff),
                       ("expert_groups", "experts", "expert_embed",
                        "expert_mlp"),
                       dtype=a.dtype, fan_in=a.d_model),
        "w2": ParamDef((a.n_groups, a.n_experts_per_group, a.d_ff, a.d_model),
                       ("expert_groups", "experts", "expert_mlp",
                        "expert_embed"),
                       dtype=a.dtype, fan_in=a.d_ff),
    }
    if gated:
        defs["w3"] = ParamDef(
            (a.n_groups, a.n_experts_per_group, a.d_model, a.d_ff),
            ("expert_groups", "experts", "expert_embed", "expert_mlp"),
            dtype=a.dtype, fan_in=a.d_model)
    return defs


def _secondary_one_group(gate_params, w1, w2, w3, x_grp, valid, a: HMoEArgs,
                         spec_s: "router_lib.RouterSpec", train: bool, rng):
    """Run one group's secondary MoE on its [Cp, d] buffer.

    ``valid`` masks the padding slots left by primary capacity dispatch —
    it is passed as the router's token-validity mask, so padded tokens
    neither route nor consume secondary capacity.  Returns (y [Cp, d],
    importance_j [b], load_j [b], n_valid scalar, telemetry dict of [b]
    counters).  Dispatch/combine and the expert FFN go through the kernel
    backend registry (vmapped over groups).
    """
    bk = backend_lib.resolve(a)
    router_s = router_lib.Router(spec_s, a.n_experts_per_group)
    cap = spec_s.capacity(x_grp.shape[0], a.n_experts_per_group,
                          train=train)
    dec = router_s.route({"gate": gate_params}, x_grp, train=train,
                         rng=rng, mask=valid, capacity=cap)
    buf = bk.dispatch(x_grp, dec, a)
    params = {"w1": w1, "w2": w2}
    if a.activation == "swiglu":
        params["w3"] = w3
    out = bk.expert_ffn(params, buf, a)
    y = bk.combine(out, dec, a, dtype=x_grp.dtype)
    importance_j = losses.importance(dec.gates)                 # [b]
    load_j = dec.load                                           # [b], masked
    n_valid = jnp.sum(valid)
    return y, importance_j, load_j, n_valid, dec.telemetry


def hmoe_apply(params, x: jax.Array, a: HMoEArgs, *, train: bool = True,
               rng: jax.Array | None = None,
               ctx: ctx_lib.MeshContext | None = None,
               mask: jax.Array | None = None
               ) -> tuple[jax.Array, dict]:
    """x: [T, d_model] -> (y [T, d_model], aux).  ``mask`` ([T] in {0,1})
    marks valid tokens (dead serving slots route nowhere)."""
    t, d = x.shape
    rng_p, rng_s = (jax.random.split(rng) if rng is not None
                    else (None, None))
    bk = backend_lib.resolve(a)     # explicit: raises on unknown/broken
    spec_p, spec_s = _level_specs(a)
    router_p = router_lib.Router(spec_p, a.n_groups,
                                 topk_impl=bk.topk_impl)
    dec_p = router_p.route({"gate": params["gate_primary"]}, x,
                           train=train, rng=rng_p, mask=mask)
    buf = bk.dispatch(x, dec_p, a, ctx=ctx)            # [a, Cp, d]
    valid = dsp.dispatch(jnp.ones((t, 1), x.dtype), dec_p.plan)[..., 0]
    valid = (valid > 0).astype(jnp.float32)            # [a, Cp]
    buf = ctx_lib.with_constraint(buf, ("expert_groups", None, "embed"),
                                  ctx)

    w3 = params.get("w3", jnp.zeros_like(params["w1"]))
    rngs = (jax.random.split(rng_s, a.n_groups) if rng_s is not None
            else None)
    sec = jax.vmap(
        lambda gp, gn, w1, w2, w3g, xg, vg, rg: _secondary_one_group(
            {"wg": gp, "wnoise": gn}, w1, w2, w3g, xg, vg, a, spec_s,
            train, rg))
    y_grp, imp_sec, load_sec, n_valid, telem_sec = sec(
        params["gate_secondary"]["wg"], params["gate_secondary"]["wnoise"],
        params["w1"], params["w2"], w3, buf, valid,
        rngs if rngs is not None else jnp.zeros((a.n_groups, 2), jnp.uint32))

    y = bk.combine(y_grp, dec_p, a, dtype=x.dtype, ctx=ctx)    # primary

    # Eq. (13): Importance_H = Gp_i * G_i_j summed over tokens.  The
    # secondary importance was computed on dispatched tokens whose combine
    # weights already include only the secondary gates, so scale by the mean
    # primary gate mass per group.
    imp_primary = losses.importance(dec_p.gates)                    # [a]
    imp_h = (imp_sec * (imp_primary /
                        jnp.maximum(n_valid, 1.0))[:, None])        # [a, b]
    # Eq. (14): Load_H = Load_p_i * Load_i / |X^(i)|.
    load_h = (dec_p.load[:, None] * load_sec /
              jnp.maximum(n_valid, 1.0)[:, None])                   # [a, b]

    aux_loss = (spec_p.w_importance * losses.cv_squared(imp_h.reshape(-1))
                + spec_p.w_load * losses.cv_squared(load_h.reshape(-1)))
    metrics = {
        "cv_importance": jnp.sqrt(losses.cv_squared(imp_h.reshape(-1))),
        "cv_load": jnp.sqrt(losses.cv_squared(load_h.reshape(-1))),
        "max_over_mean_load": jnp.max(load_h) / jnp.maximum(
            jnp.mean(load_h), 1e-9),
        "fraction_dropped": dec_p.plan.fraction_dropped,
    }
    # Serving telemetry over the flattened (group, expert) grid; primary-
    # level drops are visible via metrics["fraction_dropped"].
    telemetry = {"expert_load": telem_sec["expert_load"].reshape(-1),
                 "overflow": telem_sec["overflow"].reshape(-1)}
    return y, {"aux_loss": aux_loss, "metrics": metrics,
               "telemetry": telemetry}
