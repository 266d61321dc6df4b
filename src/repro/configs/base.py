"""Model configuration schema + architecture registry.

Every assigned architecture is a ``ModelConfig``; the transformer stack
interprets it through ``layer_kinds(cfg)`` which expands the per-period
layer pattern (attention vs mamba mixers, dense vs MoE FFNs, local vs
global attention) into one :class:`LayerKind` per position-in-period.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax.numpy as jnp

from repro.core.router import DEFAULT_CAPACITY_FACTOR, RouterSpec


@dataclasses.dataclass(frozen=True)
class LayerKind:
    mixer: str = "attn"        # attn | attn_local | mamba | moa
    ffn: str = "dense"         # dense | moe | moe+dense | none


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    vocab_size: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    # --- layer pattern -----------------------------------------------------
    period: int = 1             # layout repeats with this period
    attn_positions: tuple[int, ...] = ()   # positions-in-period that are attn
                                           # (ssm/hybrid only; dense = all)
    global_attn_positions: tuple[int, ...] = ()  # gemma-style local:global
    sliding_window: int = 0
    moe_positions: tuple[int, ...] = ()    # positions-in-period with MoE FFN
    # --- MoE ----------------------------------------------------------------
    n_experts: int = 0
    moe_k: int = 0
    moe_d_ff: int = 0
    moe_hierarchical: tuple[int, int] | None = None   # (groups, per-group)
    dense_residual: bool = False           # arctic: MoE + parallel dense FFN
    # The one routing configuration path (docs/routing.md): a RouterSpec
    # carrying policy/k/capacity/noise/balance weights.  None resolves the
    # deprecated fields below (gating_mode/capacity_factor/...) into one;
    # the spec's k inherits moe_k.
    router: RouterSpec | None = None
    # Deprecated routing spellings (router.resolve_spec shim).  The
    # capacity default is unified in RouterSpec (this used to say 1.25
    # while MoEArgs said 2.0 — two disagreeing defaults for one knob).
    capacity_factor: float = DEFAULT_CAPACITY_FACTOR
    w_importance: float = 0.1              # paper §C.1 defaults
    w_load: float = 0.1
    gating_mode: str = "noisy_topk"
    moe_wide_dispatch: bool = True         # §3.1 combined-batch resharding
    # --- MoA (Mixture-of-Attention-Heads; core/moa.py, docs/moa.md) ---------
    # Positions-in-period whose *mixer* is a routed head-group layer:
    # n_experts groups of moa_heads_per_expert query heads, k per token,
    # shared K/V (n_kv_heads, MQA-style — the KV cache is a plain
    # attention cache).  Routing defaults to the FFN RouterSpec path;
    # moa_router overrides it independently of the FFN router.
    moa_positions: tuple[int, ...] = ()
    moa_experts: int = 0
    moa_k: int = 0
    moa_heads_per_expert: int = 0
    moa_router: RouterSpec | None = None
    # --- attention ----------------------------------------------------------
    qk_norm: bool = False
    rope_theta: float = 10000.0
    # pad query heads (with zero-weight heads, sliced off before the output
    # projection) up to this count so they divide the model axis — the
    # §Perf fix for 56-head arctic on a 16-wide TP axis (1.14x padded
    # FLOPs instead of 16x replication).
    pad_attn_heads: int = 0
    # --- ssm ----------------------------------------------------------------
    ssm_d_state: int = 0
    ssm_d_conv: int = 4
    ssm_expand: int = 2
    # --- modality frontend stub ----------------------------------------------
    frontend: str = "none"      # none | vision | audio
    n_prefix: int = 0           # prefix embedding slots fed by the stub
    # --- misc ----------------------------------------------------------------
    activation: str = "swiglu"
    norm_eps: float = 1e-6
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    scan_layers: bool = True       # False: unroll (XLA cost validation)
    # attention blocking (perf knobs; see EXPERIMENTS.md §Perf)
    q_block: int = 512
    kv_block: int = 512
    expert_impl: str = "einsum"            # legacy spelling of kernel_backend
    dispatch_impl: str = "sort"
    # Kernel backend for the MoE hot path ("ref" | "pallas"); None derives
    # from expert_impl.  See src/repro/kernels/backend.py and docs/kernels.md.
    kernel_backend: str | None = None
    # VMEM budget (bytes) for the fused dispatch/combine kernels; None =
    # kernels.dispatch.DEFAULT_VMEM_LIMIT.  Past it the pallas backend
    # E-blocks the buffer ([e_block, C, d] slabs) instead of bailing to
    # the ref scatter; see docs/kernels.md §E-blocked dispatch.
    dispatch_vmem_limit: int | None = None
    # Force a fused dispatch/combine slab size; None auto-selects against
    # the VMEM budget.
    dispatch_e_block: int | None = None
    # Size GMM tiles by the tiling table, else the tile rule
    # (docs/kernels.md §Tiling autotune); False pins static 128 tiles.
    gmm_autotune: bool = True
    # Serve-time fused decode step (docs/kernels.md §Fused decode step):
    # decode-shaped MoE/MoA calls run routing + dispatch + expert FFN +
    # combine as ONE kernel launch per layer.  Inference-only — train and
    # prefill paths ignore it; outputs match the unfused path within
    # float tolerance.
    fused_decode: bool = False

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch run 500k-token contexts? (ssm/hybrid/sliding-win)."""
        if self.family in ("ssm", "hybrid"):
            return True
        return bool(self.sliding_window and self.global_attn_positions !=
                    tuple(range(self.period)))


def layer_kinds(cfg: ModelConfig) -> list[LayerKind]:
    """One LayerKind per position-in-period."""
    kinds = []
    for p in range(cfg.period):
        if cfg.family == "ssm":
            mixer = "mamba"
        elif cfg.family == "hybrid":
            mixer = "attn" if p in cfg.attn_positions else "mamba"
        elif cfg.sliding_window and cfg.global_attn_positions:
            mixer = "attn" if p in cfg.global_attn_positions else "attn_local"
        else:
            mixer = "attn"
        if p in cfg.moa_positions:
            # Loud fallback for unsupported combos (docs/moa.md): MoA is
            # an attention mixer — it cannot replace an ssm state scan,
            # and it has no sliding-window variant.
            if mixer == "mamba":
                raise ValueError(
                    f"moa_positions={cfg.moa_positions}: position {p} is "
                    f"an ssm mixer in family {cfg.family!r}; MoA routes "
                    "attention head groups and cannot replace a state-"
                    "space scan (put MoA on an attn position)")
            if mixer == "attn_local":
                raise ValueError(
                    f"moa_positions={cfg.moa_positions}: position {p} is "
                    "a sliding-window local-attention layer; MoA has no "
                    "windowed variant (use a global_attn_positions slot)")
            if cfg.moa_experts < 2 or cfg.moa_k < 1 \
                    or cfg.moa_heads_per_expert < 1:
                raise ValueError(
                    "moa_positions set but moa_experts/moa_k/"
                    "moa_heads_per_expert are not configured "
                    f"(got {cfg.moa_experts}/{cfg.moa_k}/"
                    f"{cfg.moa_heads_per_expert})")
            mixer = "moa"
        if cfg.family == "ssm":
            ffn = "none"                     # pure mamba blocks have no FFN
        elif p in cfg.moe_positions:
            ffn = "moe+dense" if cfg.dense_residual else "moe"
        elif cfg.d_ff > 0:
            ffn = "dense"
        else:
            ffn = "none"
        kinds.append(LayerKind(mixer=mixer, ffn=ffn))
    return kinds


def n_periods(cfg: ModelConfig) -> tuple[int, int]:
    """(full scanned periods, remainder/unrolled layers)."""
    if not cfg.scan_layers:
        return 0, cfg.n_layers
    return divmod(cfg.n_layers, cfg.period)[0], cfg.n_layers % cfg.period


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Callable[[], ModelConfig]] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_config(name: str, **overrides) -> ModelConfig:
    import repro.configs  # noqa: F401  (populates the registry)
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    cfg = _REGISTRY[name]()
    return cfg.replace(**overrides) if overrides else cfg


def list_configs() -> list[str]:
    import repro.configs  # noqa: F401
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Parameter accounting (Table 1/7-style reporting + MODEL_FLOPS)
# ---------------------------------------------------------------------------

def count_params(cfg: ModelConfig) -> dict:
    """Analytic parameter counts (total / active per token)."""
    d = cfg.d_model
    kinds = layer_kinds(cfg)
    full, rem = n_periods(cfg)
    total = emb = 2 * cfg.vocab_size * d
    active = emb
    gated = cfg.activation in ("swiglu", "geglu")
    per_pos_counts = []
    for kind in kinds:
        c_total = c_active = 0
        if kind.mixer in ("attn", "attn_local"):
            c = d * cfg.head_dim * (2 * cfg.n_heads + 2 * cfg.n_kv_heads)
            c_total += c
            c_active += c
        elif kind.mixer == "moa":
            hg = cfg.moa_heads_per_expert * cfg.head_dim
            per_e = 2 * d * hg                      # wq + wo per head group
            shared = 2 * d * max(cfg.n_kv_heads, 1) * cfg.head_dim + \
                d * cfg.moa_experts                 # wk/wv + gate
            c_total += cfg.moa_experts * per_e + shared
            c_active += cfg.moa_k * per_e + shared
        elif kind.mixer == "mamba":
            d_in = cfg.ssm_expand * d
            r = -(-d // 16)
            c = (d * 2 * d_in + cfg.ssm_d_conv * d_in
                 + d_in * (r + 2 * cfg.ssm_d_state) + r * d_in
                 + d_in * cfg.ssm_d_state + d_in * d)
            c_total += c
            c_active += c
        if kind.ffn in ("dense",):
            c = d * cfg.d_ff * (3 if gated else 2)
            c_total += c
            c_active += c
        if kind.ffn in ("moe", "moe+dense"):
            per_e = d * cfg.moe_d_ff * (3 if gated else 2)
            c_total += cfg.n_experts * per_e
            c_active += cfg.moe_k * per_e
            if kind.ffn == "moe+dense":
                c = d * cfg.d_ff * (3 if gated else 2)
                c_total += c
                c_active += c
        per_pos_counts.append((c_total, c_active))
    for i, (ct, ca) in enumerate(per_pos_counts):
        reps = full + (1 if i < rem else 0)
        total += reps * ct
        active += reps * ca
    return {"total": total, "active": active,
            "total_excl_embed": total - emb,
            "active_excl_embed": active - emb}
