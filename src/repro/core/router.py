"""First-class Router API: RouterSpec + policy registry + RouteDecision.

The paper's core contribution is the *trainable gating network* (§2, §4,
Appendix A), so routing deserves the same first-class treatment the kernel
hot path got from ``repro.kernels.backend``: one typed spec, one registry,
one resolution point — instead of ``gating_mode`` / ``dispatch_impl`` /
``capacity_factor`` strings and floats spread (with disagreeing defaults)
across ``MoEArgs``, ``HMoEArgs`` and ``ModelConfig``.

* :class:`RouterSpec` — a frozen value object holding *everything* that
  configures a routing decision: policy name, k, train/eval capacity
  factors, noise, balance-loss weights, and the dispatch scatter flavour.
  ``ModelConfig.router`` / ``MoEArgs.router`` / ``HMoEArgs.router`` carry
  one; the legacy string fields are a deprecated shim that
  :func:`resolve_spec` folds into a spec (with a ``DeprecationWarning``
  for the old spellings).
* the **policy registry** — ``register_policy`` / ``get_policy``, exactly
  analogous to the kernel-backend registry: resolution is explicit and an
  unknown policy raises :class:`RouterError` (never a silent default).
  Built-ins: ``noisy_topk`` (Eqs. 3-5 + Appendix-A load), ``batchwise``
  and ``threshold`` (Appendix F), and ``expert_choice`` (experts pick
  tokens — capacity-bound by construction, Zhou et al. 2022), the proof
  that new routing scenarios land as one registered function instead of
  edits to moe.py/hierarchical.py/configs in lockstep.
* :class:`Router` / :class:`RouteDecision` — ``router.route(params, x,
  train=..., mask=...)`` returns the full typed routing decision: combine
  weights, expert indices, the capacity-dispatch plan, balancing losses,
  balance metrics and serving telemetry.  ``moe_apply`` / ``hmoe_apply``
  and the expert-parallel schedule consume it; the kernel backends accept
  a decision wherever they accept a plan.

Token-validity masking: ``route(..., mask=valid)`` (``[T]`` in {0,1})
zeroes masked tokens out of gates, load, telemetry *and* capacity — a
masked token's assignments sort behind every real token and take no
buffer slot.  The serving engine uses this to stop dead slots from
consuming expert capacity, and bucketed prefill uses it to keep padded
prompt tails out of routing (docs/routing.md).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import dispatch as dsp
from repro.core import gating, losses

# The single capacity-factor default.  ModelConfig used to say 1.25 while
# MoEArgs said 2.0; the paper-LM config (§C.1) trains at 2.0 and that is
# the value every carrier now inherits unless it sets one explicitly
# (tests/test_router.py pins the resolved value for the paper config).
DEFAULT_CAPACITY_FACTOR = 2.0


class RouterError(ValueError):
    """Unknown routing policy or invalid router configuration."""


# ---------------------------------------------------------------------------
# the spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RouterSpec:
    """Everything that configures one routing decision.

    ``k`` may be ``None`` to inherit the carrier's value (``MoEArgs.k`` /
    ``ModelConfig.moe_k`` / the per-level k of ``HMoEArgs``), since k also
    sizes parameter definitions and analytic accounting there.
    ``eval_capacity_factor=None`` means "same as training".
    """
    policy: str = "noisy_topk"
    k: int | None = None
    capacity_factor: float = DEFAULT_CAPACITY_FACTOR
    eval_capacity_factor: float | None = None
    noise: bool = True              # Eq. (3) tunable Gaussian noise (train)
    w_importance: float = 0.1       # §C.1 defaults for Eqs. (7)/(11)
    w_load: float = 0.1
    dispatch: str = "sort"          # ref-backend scatter: sort | einsum
    priority_dispatch: bool = False  # over-capacity slots by weight, not order
    capacity_multiple: int = 8      # TPU tiling round-up for capacity

    def replace(self, **kw) -> "RouterSpec":
        return dataclasses.replace(self, **kw)

    @property
    def eval_cf(self) -> float:
        return (self.capacity_factor if self.eval_capacity_factor is None
                else self.eval_capacity_factor)

    def capacity(self, n_tokens: int, n_experts: int, *,
                 train: bool) -> int:
        """Slots per expert for a batch of ``n_tokens`` (ceil + tiling)."""
        cf = self.capacity_factor if train else self.eval_cf
        return dsp.capacity_for(n_tokens, n_experts, self.k or 1, cf,
                                multiple=self.capacity_multiple)


# ---------------------------------------------------------------------------
# the decision
# ---------------------------------------------------------------------------

class RouteDecision(NamedTuple):
    """The full typed result of one routing decision."""
    combine_weights: jax.Array   # [T, k] f32 gate values of the winners
    expert_index: jax.Array      # [T, k] int32 winning experts
    gates: jax.Array             # [T, E] f32 sparse gate matrix G(x)
    load: jax.Array              # [E] f32 (smooth) load estimator
    plan: dsp.DispatchPlan       # capacity dispatch plan (post-truncation)
    aux_loss: jax.Array          # §4 balancing losses, already weighted
    metrics: dict                # Table-6 diagnostics + fraction_dropped
    telemetry: dict              # serving counters: expert_load / overflow


def route_telemetry(info: gating.GatingInfo, p: dsp.DispatchPlan) -> dict:
    """Per-expert serving counters from one gating/dispatch decision.

    ``expert_load``: hard assignment counts (tokens routed per expert),
    ``overflow``: assignments dropped by capacity truncation per expert.
    Masked (zero-weight) tokens count toward neither.
    """
    assigned = (info.combine_weights > 0.0).reshape(-1)
    kept = (p.position < p.capacity).reshape(-1)
    flat_e = info.expert_index.reshape(-1)
    zero = jnp.zeros((p.n_experts,), jnp.float32)
    return {
        "expert_load": zero.at[flat_e].add(assigned.astype(jnp.float32)),
        "overflow": zero.at[flat_e].add(
            (assigned & ~kept).astype(jnp.float32)),
    }


# ---------------------------------------------------------------------------
# policy registry
# ---------------------------------------------------------------------------

class PolicyOutput(NamedTuple):
    """What a policy hands back to the Router.

    ``capacity``/``plan`` are overrides: ``None`` lets the Router derive
    the capacity from the spec and build the standard dispatch plan.
    ``extra_loss`` joins the importance/load losses (e.g. the Appendix-F
    threshold-alignment loss, Eq. 20).
    """
    info: gating.GatingInfo
    capacity: int | None = None
    plan: dsp.DispatchPlan | None = None
    extra_loss: jax.Array | float = 0.0


@dataclasses.dataclass(frozen=True)
class RouterPolicy:
    """One registered routing policy.

    ``route(params, x, spec, n_experts, *, train, rng, mask, capacity,
    topk_impl) -> PolicyOutput``; ``defs(spec, d_model, n_experts)``
    returns the policy's parameter definitions (merged into the MoE
    layer's defs — e.g. ``{"gate": ...}`` plus Appendix-F thresholds).
    """
    name: str
    route: Callable
    defs: Callable


_POLICIES: dict[str, RouterPolicy] = {}


def register_policy(policy: RouterPolicy) -> None:
    _POLICIES[policy.name] = policy


def available_policies() -> list[str]:
    return sorted(_POLICIES)


def get_policy(name: str) -> RouterPolicy:
    entry = _POLICIES.get(name)
    if entry is None:
        raise RouterError(
            f"unknown router policy {name!r}; registered: "
            f"{sorted(_POLICIES)}")
    return entry


# ---------------------------------------------------------------------------
# legacy-string resolution (the deprecation shim)
# ---------------------------------------------------------------------------

_LEGACY_STRINGS = ("gating_mode", "dispatch_impl", "expert_impl")
_LEGACY_DEFAULTS = {"gating_mode": "noisy_topk", "dispatch_impl": "sort",
                    "expert_impl": "einsum"}


def _warn_legacy(a) -> None:
    used = [f for f in _LEGACY_STRINGS
            if getattr(a, f, _LEGACY_DEFAULTS[f]) != _LEGACY_DEFAULTS[f]]
    if used:
        warnings.warn(
            f"{type(a).__name__} fields {used} are deprecated string "
            "spellings; pass a repro.core.router.RouterSpec (router=...) "
            "instead (docs/routing.md)", DeprecationWarning, stacklevel=3)


def resolve_spec(a) -> RouterSpec:
    """The single resolution point: carrier (MoEArgs / HMoEArgs /
    ModelConfig / PaperLMConfig) -> a validated RouterSpec.

    An explicit ``a.router`` wins; otherwise the legacy fields resolve
    into a spec (``DeprecationWarning`` for non-default string
    spellings).  ``k=None`` inherits the carrier's k.  The policy name is
    validated against the registry — unknown policies raise RouterError.
    """
    spec = getattr(a, "router", None)
    if spec is None:
        _warn_legacy(a)
        cf = getattr(a, "capacity_factor", None)
        spec = RouterSpec(
            policy=getattr(a, "gating_mode", "noisy_topk"),
            capacity_factor=DEFAULT_CAPACITY_FACTOR if cf is None else cf,
            eval_capacity_factor=getattr(a, "eval_capacity_factor", None),
            w_importance=getattr(a, "w_importance", 0.1),
            w_load=getattr(a, "w_load", 0.1),
            dispatch=getattr(a, "dispatch_impl", "sort"),
            priority_dispatch=getattr(a, "priority_dispatch", False))
    if spec.k is None:
        k = getattr(a, "k", None)
        if k is None:
            k = getattr(a, "moe_k", None)
        if k:
            spec = spec.replace(k=int(k))
    get_policy(spec.policy)     # explicit: unknown policy raises here
    return spec


# ---------------------------------------------------------------------------
# the Router
# ---------------------------------------------------------------------------

class Router:
    """A resolved (spec, n_experts) pair with a callable ``route``.

    ``topk_impl`` is the kernel backend's fused KeepTopK+softmax (or
    ``None`` for the lax.top_k path) — the only coupling between routing
    and the kernel registry, passed in so this module imports neither.
    """

    def __init__(self, spec: RouterSpec, n_experts: int, *,
                 topk_impl: Callable | None = None):
        if spec.k is None:
            raise RouterError(f"RouterSpec.k unresolved for {spec}")
        self.spec = spec
        self.n_experts = n_experts
        self.policy = get_policy(spec.policy)
        self.topk_impl = topk_impl

    def gate_defs(self, d_model: int) -> dict:
        """Parameter definitions this policy needs (merged into moe_defs)."""
        return self.policy.defs(self.spec, d_model, self.n_experts)

    def capacity(self, n_tokens: int, *, train: bool) -> int:
        return self.spec.capacity(n_tokens, self.n_experts, train=train)

    @jax.named_scope("router")
    def route(self, params, x: jax.Array, *, train: bool,
              rng: jax.Array | None = None,
              mask: jax.Array | None = None,
              capacity: int | None = None) -> RouteDecision:
        """One routing decision over a flat token batch x: [T, d].

        ``mask`` ([T] in {0,1}) marks valid tokens: masked tokens get
        zero gate weight, zero load, zero telemetry, and consume no
        expert capacity.  ``capacity`` overrides the spec-derived
        slots-per-expert (the hierarchical secondary level does this).
        """
        spec = self.spec
        if mask is not None:
            mask = jnp.asarray(mask, jnp.float32).reshape(-1)
        if capacity is None:
            capacity = self.capacity(x.shape[0], train=train)
        out = self.policy.route(params, x, spec, self.n_experts,
                                train=train, rng=rng, mask=mask,
                                capacity=capacity,
                                topk_impl=self.topk_impl)
        info = out.info
        plan = out.plan
        if plan is None:
            cap = capacity if out.capacity is None else out.capacity
            plan = dsp.plan(info.expert_index, info.combine_weights,
                            self.n_experts, cap,
                            priority=spec.priority_dispatch)
        aux_loss = (losses.importance_loss(info.gates, spec.w_importance)
                    + losses.load_loss(info.load, spec.w_load)
                    + out.extra_loss)
        metrics = losses.balance_metrics(info.gates, info.load)
        metrics["fraction_dropped"] = plan.fraction_dropped
        return RouteDecision(
            combine_weights=info.combine_weights,
            expert_index=info.expert_index, gates=info.gates,
            load=info.load, plan=plan, aux_loss=aux_loss,
            metrics=metrics, telemetry=route_telemetry(info, plan))


def build(a, *, topk_impl: Callable | None = None) -> Router:
    """Carrier args -> Router (resolve_spec + n_experts), the one-liner
    ``moe_apply``/``hmoe_apply``/the EP schedule use."""
    return Router(resolve_spec(a), a.n_experts, topk_impl=topk_impl)


# ---------------------------------------------------------------------------
# built-in policies
# ---------------------------------------------------------------------------

def _gate_only_defs(spec: RouterSpec, d_model: int, n_experts: int) -> dict:
    return {"gate": gating.gating_defs(d_model, n_experts, noisy=False)}


def _noisy_topk_defs(spec: RouterSpec, d_model: int, n_experts: int) -> dict:
    return {"gate": gating.gating_defs(d_model, n_experts,
                                       noisy=spec.noise)}


def _noisy_topk_route(params, x, spec, n_experts, *, train, rng, mask,
                      capacity, topk_impl) -> PolicyOutput:
    """Eqs. (3)-(5) + the Appendix-A load estimator."""
    info = gating.noisy_topk_gating(
        params["gate"], x, spec.k, train=train and spec.noise,
        rng=rng if spec.noise else None, valid=mask, topk_impl=topk_impl)
    return PolicyOutput(info=info)


def _appendix_f_capacity(spec: RouterSpec, n_tokens: int,
                         n_experts: int) -> int:
    """Appendix F: exactly m = k·T/E slots per expert; nothing dropped."""
    cap = max((spec.k * n_tokens) // n_experts, 1)
    m = spec.capacity_multiple
    return int(-(-cap // m) * m)


def _batchwise_route(params, x, spec, n_experts, *, train, rng, mask,
                     capacity, topk_impl) -> PolicyOutput:
    info = gating.batchwise_gating(params["gate"], x, spec.k, valid=mask)
    cap = (_appendix_f_capacity(spec, x.shape[0], n_experts) if train
           else None)
    return PolicyOutput(info=info, capacity=cap)


def _threshold_defs(spec: RouterSpec, d_model: int, n_experts: int) -> dict:
    return {"gate": gating.gating_defs(d_model, n_experts, noisy=False),
            "thresholds": gating.threshold_defs(n_experts)}


def _threshold_route(params, x, spec, n_experts, *, train, rng, mask,
                     capacity, topk_impl) -> PolicyOutput:
    if train:   # train with the batchwise mask, infer with thresholds
        info = gating.batchwise_gating(params["gate"], x, spec.k,
                                       valid=mask)
        extra = gating.batchwise_threshold_loss(
            params["gate"], params["thresholds"], x, spec.k)
        cap = _appendix_f_capacity(spec, x.shape[0], n_experts)
        return PolicyOutput(info=info, capacity=cap, extra_loss=extra)
    info = gating.threshold_gating(params["gate"], params["thresholds"],
                                   x, spec.k, valid=mask)
    return PolicyOutput(info=info)


def _expert_choice_route(params, x, spec, n_experts, *, train, rng, mask,
                         capacity, topk_impl) -> PolicyOutput:
    """Expert-choice routing (Zhou et al. 2022): experts pick tokens.

    Each expert selects its top-``capacity`` tokens by gate affinity, so
    the dispatch buffers are full-by-construction and *nothing ever
    overflows* — the positions assigned here are column ranks < capacity.
    A token keeps at most ``spec.k`` of the experts that picked it (the
    token-major [T, k] interface the dispatch plan and kernels share);
    picks beyond that per-token width are reported as
    ``fraction_dropped``.  Masked tokens are never picked.
    """
    t = x.shape[0]
    xf = jnp.asarray(x, jnp.float32)
    logits = xf @ jnp.asarray(params["gate"]["wg"], jnp.float32)   # [T, E]
    g_dense = jax.nn.softmax(logits, axis=-1)
    g_pickable = g_dense if mask is None else g_dense * mask[:, None]

    cap = min(capacity, t)
    # Per-expert top-C tokens over the batch (columns of g).
    col_vals, col_idx = jax.lax.top_k(g_pickable.T, cap)           # [E, C]
    # Rank (= buffer position) of each picked token within its expert.
    e_rows = jnp.broadcast_to(jnp.arange(n_experts)[:, None],
                              (n_experts, cap))
    picked = jnp.zeros((t, n_experts), bool).at[
        col_idx, e_rows].set(col_vals > 0.0)                       # [T, E]
    pos_matrix = jnp.full((t, n_experts), capacity, jnp.int32).at[
        col_idx, e_rows].set(
        jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32)[None, :],
                         (n_experts, cap)))                        # [T, E]

    # Token-major view: each token keeps its k best picking experts.
    kk = min(spec.k, n_experts)
    g_kept = jnp.where(picked, g_dense, 0.0)
    combine, topk_idx = jax.lax.top_k(g_kept, kk)                  # [T, k]
    topk_idx = topk_idx.astype(jnp.int32)
    position = jnp.take_along_axis(pos_matrix, topk_idx, axis=1)
    position = jnp.where(combine > 0.0, position, capacity)

    gates = jnp.zeros_like(g_dense).at[
        jnp.arange(t)[:, None], topk_idx].set(combine)
    load = jnp.sum(picked.astype(jnp.float32), axis=0)             # [E]

    n_picks = jnp.maximum(jnp.sum(picked.astype(jnp.float32)), 1.0)
    kept = jnp.sum((combine > 0.0).astype(jnp.float32))
    plan = dsp.DispatchPlan(
        expert_index=topk_idx, position=position,
        weight=combine.astype(jnp.float32), n_experts=n_experts,
        capacity=capacity,
        fraction_dropped=(n_picks - kept) / n_picks)
    info = gating.GatingInfo(
        combine_weights=combine, expert_index=topk_idx, gates=gates,
        load=load, raw_logits=logits)
    return PolicyOutput(info=info, plan=plan)


register_policy(RouterPolicy(name="noisy_topk", route=_noisy_topk_route,
                             defs=_noisy_topk_defs))
register_policy(RouterPolicy(name="batchwise", route=_batchwise_route,
                             defs=_gate_only_defs))
register_policy(RouterPolicy(name="threshold", route=_threshold_route,
                             defs=_threshold_defs))
register_policy(RouterPolicy(name="expert_choice",
                             route=_expert_choice_route,
                             defs=_gate_only_defs))
