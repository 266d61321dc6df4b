"""Decoder blocks + the period-stacked layer scan.

Layers repeat with a per-arch *period* (gemma3: 5 local + 1 global = 6;
jamba: 7 mamba + 1 attn with MoE on odd positions = 8; homogeneous archs:
1).  Parameters for each position-in-period are stacked across periods and
the stack runs under one ``lax.scan`` — keeping HLO size O(period) instead
of O(n_layers), which is what makes 61-64-layer models compile fast and
lets one remat policy wrap the whole scan body (the paper's Appendix-D
"recompute expert activations on the backward pass" falls out of this).
Remainder layers (gemma3's 62 = 6·10 + 2) run unrolled as a tail.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import param as pm
from repro.common.param import ParamDef
from repro.configs.base import LayerKind, ModelConfig, layer_kinds, n_periods
from repro.core import expert_parallel as ep_lib
from repro.core import hierarchical as hmoe
from repro.core import moa as moa_lib
from repro.core import moe as moe_lib
from repro.kernels import backend as backend_lib
from repro.models import attention, layers, ssm
from repro.sharding import context as ctx_lib


def _moe_args(cfg: ModelConfig, *, decode: bool = False) -> moe_lib.MoEArgs:
    # ``decode`` marks a decode-shaped call: only those opt in to the
    # fused single-launch decode step (train/prefill stay unfused).
    return moe_lib.MoEArgs(
        n_experts=cfg.n_experts, k=cfg.moe_k, d_model=cfg.d_model,
        d_ff=cfg.moe_d_ff, activation=cfg.activation,
        router=cfg.router,
        gating_mode=cfg.gating_mode, capacity_factor=cfg.capacity_factor,
        w_importance=cfg.w_importance, w_load=cfg.w_load,
        dispatch_impl=cfg.dispatch_impl, expert_impl=cfg.expert_impl,
        kernel_backend=cfg.kernel_backend,
        dispatch_vmem_limit=cfg.dispatch_vmem_limit,
        dispatch_e_block=cfg.dispatch_e_block,
        gmm_autotune=cfg.gmm_autotune,
        fused_decode=cfg.fused_decode and decode,
        wide_dispatch=cfg.moe_wide_dispatch, dtype=cfg.param_dtype)


def _hmoe_args(cfg: ModelConfig) -> hmoe.HMoEArgs:
    a, b = cfg.moe_hierarchical
    return hmoe.HMoEArgs(
        n_groups=a, n_experts_per_group=b, k_primary=cfg.moe_k,
        k_secondary=cfg.moe_k, d_model=cfg.d_model, d_ff=cfg.moe_d_ff,
        activation=cfg.activation, router=cfg.router,
        capacity_factor=cfg.capacity_factor,
        w_importance=cfg.w_importance, w_load=cfg.w_load,
        kernel_backend=cfg.kernel_backend, dispatch_impl=cfg.dispatch_impl,
        dispatch_vmem_limit=cfg.dispatch_vmem_limit,
        dispatch_e_block=cfg.dispatch_e_block,
        gmm_autotune=cfg.gmm_autotune, dtype=cfg.param_dtype)


def _moa_args(cfg: ModelConfig, *, decode: bool = False) -> moa_lib.MoAArgs:
    # The FFN RouterSpec is reused for MoA policy/capacity knobs unless
    # moa_router overrides it — but its k is the FFN's k, so strip it and
    # let resolve_spec re-inherit from MoAArgs.k (= cfg.moa_k).
    router = cfg.moa_router
    if router is None and cfg.router is not None:
        router = cfg.router.replace(k=None)
    return moa_lib.MoAArgs(
        n_experts=cfg.moa_experts, k=cfg.moa_k, d_model=cfg.d_model,
        n_heads_per_expert=cfg.moa_heads_per_expert, head_dim=cfg.head_dim,
        n_kv_heads=max(cfg.n_kv_heads, 1), qk_norm=cfg.qk_norm,
        rope_theta=cfg.rope_theta,
        router=router,
        capacity_factor=cfg.capacity_factor,
        w_importance=cfg.w_importance, w_load=cfg.w_load,
        kernel_backend=cfg.kernel_backend, dispatch_impl=cfg.dispatch_impl,
        dispatch_vmem_limit=cfg.dispatch_vmem_limit,
        dispatch_e_block=cfg.dispatch_e_block,
        gmm_autotune=cfg.gmm_autotune,
        fused_decode=cfg.fused_decode and decode,
        q_block=cfg.q_block, kv_block=cfg.kv_block, dtype=cfg.param_dtype)


def block_defs(cfg: ModelConfig, kind: LayerKind) -> dict:
    defs: dict = {"ln1": layers.rmsnorm_defs(cfg.d_model)}
    if kind.mixer in ("attn", "attn_local"):
        defs["attn"] = attention.attention_defs(
            cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qk_norm=cfg.qk_norm, dtype=cfg.param_dtype)
    elif kind.mixer == "moa":
        defs["moa"] = moa_lib.moa_defs(_moa_args(cfg))
    else:
        defs["mamba"] = ssm.mamba_defs(
            cfg.d_model, d_state=cfg.ssm_d_state, d_conv=cfg.ssm_d_conv,
            expand=cfg.ssm_expand, dtype=cfg.param_dtype)
    if kind.ffn != "none":
        defs["ln2"] = layers.rmsnorm_defs(cfg.d_model)
    if kind.ffn in ("moe", "moe+dense"):
        if cfg.moe_hierarchical:
            defs["moe"] = hmoe.hmoe_defs(_hmoe_args(cfg))
        else:
            defs["moe"] = moe_lib.moe_defs(_moe_args(cfg))
    if kind.ffn in ("dense", "moe+dense"):
        defs["mlp"] = layers.mlp_defs(cfg.d_model, cfg.d_ff, cfg.activation,
                                      cfg.param_dtype)
    return defs


_ZERO_METRICS = ("cv_importance", "cv_load", "max_over_mean_load",
                 "fraction_dropped")


def _zero_aux():
    return {"aux_loss": jnp.zeros((), jnp.float32),
            "metrics": {k: jnp.zeros((), jnp.float32)
                        for k in _ZERO_METRICS},
            "n_moe": jnp.zeros((), jnp.float32)}


def _add_aux(acc, aux):
    # aux["n"] is the number of routed sublayers the entry sums over — a
    # block with an MoA mixer *and* an MoE FFN contributes 2 (metrics are
    # averaged over n_moe in lm_loss, so the count must match the sums).
    return {"aux_loss": acc["aux_loss"] + aux["aux_loss"],
            "metrics": {k: acc["metrics"][k] + aux["metrics"][k]
                        for k in _ZERO_METRICS},
            "n_moe": acc["n_moe"] + aux.get("n", 1.0)}


def _merge_aux(a, b):
    """Merge the mixer's and the FFN's per-layer aux (either may be None).
    Telemetry dicts merge by key — MoA entries use moa_load/moa_overflow,
    MoE entries expert_load/overflow, so both survive side by side."""
    if a is None:
        return b
    if b is None:
        return a
    out = {"aux_loss": a["aux_loss"] + b["aux_loss"],
           "metrics": {k: a["metrics"][k] + b["metrics"][k]
                       for k in _ZERO_METRICS},
           "n": a.get("n", 1.0) + b.get("n", 1.0)}
    ta = a.get("telemetry") or {}
    tb = b.get("telemetry") or {}
    if ta or tb:
        out["telemetry"] = {**ta, **tb}
    return out


def _moa_aux(aux):
    """Adapt an MoA layer's router aux: rename the telemetry counters so
    head-group load is never summed into FFN-expert load (the vectors can
    even differ in length)."""
    t = aux.get("telemetry")
    out = {"aux_loss": aux["aux_loss"], "metrics": aux["metrics"],
           "n": 1.0}
    if t is not None:
        out["telemetry"] = {"moa_load": t["expert_load"],
                            "moa_overflow": t["overflow"]}
    return out


def _flat_mask(valid, b, s):
    """[B] or [B, S] validity -> flat [B·S] float routing mask (None
    passes through)."""
    if valid is None:
        return None
    return jnp.broadcast_to(
        jnp.asarray(valid, jnp.float32).reshape(
            (b, -1) if jnp.ndim(valid) > 1 else (b, 1)),
        (b, s)).reshape(b * s)


# ---------------------------------------------------------------------------
# Serving telemetry: per-expert load / overflow counters summed over the
# MoE layers of one decode (or prefill) step.  The train path drops the
# per-layer "telemetry" entry in _add_aux; the decode stack accumulates it
# so serving skew is observable per step.
# ---------------------------------------------------------------------------

def telemetry_width(cfg: ModelConfig) -> int:
    """Length of the per-expert telemetry vectors (0 = model has no MoE)."""
    if not any(k.ffn in ("moe", "moe+dense") for k in layer_kinds(cfg)):
        return 0
    if cfg.moe_hierarchical:
        a, b = cfg.moe_hierarchical
        return a * b
    return cfg.n_experts


def moa_telemetry_width(cfg: ModelConfig) -> int:
    """Length of the per-head-group telemetry vectors (0 = no MoA mixer)."""
    if not any(k.mixer == "moa" for k in layer_kinds(cfg)):
        return 0
    return cfg.moa_experts


def _telemetry_zero(cfg: ModelConfig):
    t = {}
    n = telemetry_width(cfg)
    if n:
        t.update(expert_load=jnp.zeros((n,), jnp.float32),
                 overflow=jnp.zeros((n,), jnp.float32),
                 n_moe=jnp.zeros((), jnp.float32))
    m = moa_telemetry_width(cfg)
    if m:
        t.update(moa_load=jnp.zeros((m,), jnp.float32),
                 moa_overflow=jnp.zeros((m,), jnp.float32),
                 n_moa=jnp.zeros((), jnp.float32))
    return t or None


def _add_telemetry(acc, aux):
    if acc is None or aux is None:
        return acc
    t = aux.get("telemetry")
    if t is None:
        return acc
    out = dict(acc)
    if "expert_load" in t and "expert_load" in acc:
        out["expert_load"] = acc["expert_load"] + t["expert_load"]
        out["overflow"] = acc["overflow"] + t["overflow"]
        out["n_moe"] = acc["n_moe"] + 1.0
    if "moa_load" in t and "moa_load" in acc:
        out["moa_load"] = acc["moa_load"] + t["moa_load"]
        out["moa_overflow"] = acc["moa_overflow"] + t["moa_overflow"]
        out["n_moa"] = acc["n_moa"] + 1.0
    return out


def _moe_schedule(a: moe_lib.MoEArgs, ctx, *, train: bool, n_tokens: int):
    """The MoE layer function for this call: plain ``moe_apply`` (GSPMD
    constraints), or one of ``core/expert_parallel.py``'s shard_map
    schedules when a backend whose kernels the SPMD partitioner cannot
    split (``KernelBackend.needs_shard_map``: compiled Mosaic kernels)
    meets a multi-device mesh, so the kernels run on per-shard blocks.

    Training on a (data, model) mesh whose experts split over model runs
    the paper's §3.1 all-to-all schedule (``moe_apply_ep``): each shard
    routes its own tokens with shard-local capacity, the data-parallel
    gating of the paper.  Serving (prefill and decode) runs
    ``moe_apply_expert_sharded``: one global routing decision, each device
    computing its own experts' share.  Training on other meshes keeps
    ``moe_apply``."""
    if (ctx is None or ctx.mesh is None or ctx.manual_axes
            or ctx.mesh.size == 1
            or not backend_lib.resolve(a).needs_shard_map):
        return moe_lib.moe_apply
    mesh = ctx.mesh
    if (train and set(mesh.axis_names) == {"data", "model"}
            and ctx.rules.lookup("experts") == ("model",)
            and a.n_experts % mesh.shape["model"] == 0
            and n_tokens % mesh.size == 0):
        return ep_lib.moe_apply_ep
    if train:
        # No shard_map training schedule for this mesh: GSPMD lowering of
        # a compiled kernel then refuses loudly ("wrap in a shard_map").
        return moe_lib.moe_apply
    return lambda p, x, a_, *, train, rng, ctx, mask: \
        ep_lib.moe_apply_expert_sharded(p, x, a_, ctx=ctx, mask=mask)


def _apply_ffn(params, x, kind: LayerKind, cfg: ModelConfig, *, train, rng,
               ctx: ctx_lib.MeshContext | None = None, valid=None,
               decode: bool = False):
    """Post-mixer FFN with residual. x: [B, S, d].

    ``valid`` ([B] or [B, S] in {0,1}) is the router's token-validity
    mask: masked tokens (dead serving slots, bucketed-prefill padding)
    neither route nor consume MoE expert capacity."""
    if kind.ffn == "none":
        return x, None
    h = layers.rmsnorm(params["ln2"], x, cfg.norm_eps)
    out = x
    aux = None
    if kind.ffn in ("moe", "moe+dense"):
        b, s, d = h.shape
        flat = h.reshape(b * s, d)
        mask = _flat_mask(valid, b, s)
        if cfg.moe_hierarchical:
            y, aux = hmoe.hmoe_apply(params["moe"], flat, _hmoe_args(cfg),
                                     train=train, rng=rng, ctx=ctx,
                                     mask=mask)
        else:
            args = _moe_args(cfg, decode=decode)
            apply = _moe_schedule(args, ctx, train=train, n_tokens=b * s)
            y, aux = apply(params["moe"], flat, args, train=train, rng=rng,
                           ctx=ctx, mask=mask)
        out = out + y.reshape(b, s, d)
    if kind.ffn in ("dense", "moe+dense"):
        out = out + layers.mlp(params["mlp"], h, cfg.activation, ctx=ctx)
    return out, aux


def block_apply(params, x, kind: LayerKind, cfg: ModelConfig, *,
                positions, rng, train: bool,
                ctx: ctx_lib.MeshContext | None = None):
    """Train/prefill block. Returns (x, aux)."""
    h = layers.rmsnorm(params["ln1"], x, cfg.norm_eps)
    aux_mix = None
    if kind.mixer in ("attn", "attn_local"):
        window = cfg.sliding_window if kind.mixer == "attn_local" else 0
        y = attention.attention(params["attn"], h, positions,
                                rope_theta=cfg.rope_theta,
                                qk_norm=cfg.qk_norm, window=window,
                                q_block=cfg.q_block, kv_block=cfg.kv_block,
                                pad_heads=cfg.pad_attn_heads, ctx=ctx)
    elif kind.mixer == "moa":
        # Fold the rng so head-group routing noise decorrelates from the
        # FFN router's noise in the same block.
        sub = jax.random.fold_in(rng, 1) if rng is not None else None
        y, a_moa = moa_lib.moa_apply(params["moa"], h, _moa_args(cfg),
                                     positions=positions, train=train,
                                     rng=sub, ctx=ctx)
        aux_mix = _moa_aux(a_moa)
    else:
        y = ssm.mamba(params["mamba"], h, d_state=cfg.ssm_d_state, ctx=ctx)
    x = x + y
    x, aux = _apply_ffn(params, x, kind, cfg, train=train, rng=rng, ctx=ctx)
    return x, _merge_aux(aux_mix, aux)


def block_prefill(params, x, kind: LayerKind, cfg: ModelConfig, cache,
                  positions,
                  ctx: ctx_lib.MeshContext | None = None, valid=None,
                  start_pos: int | None = None):
    """Prefill block: causal attention + cache fill. Returns (x, cache).
    ``valid`` ([B, S]) keeps bucketed-prefill padding out of MoE routing.
    ``start_pos`` (static int) runs the block in chunked-prefill mode:
    K/V land at cache positions [start_pos, start_pos + S) and attention
    resumes against the cached prefix (attention mixers only — ssm state
    scans cannot resume from a cache page)."""
    h = layers.rmsnorm(params["ln1"], x, cfg.norm_eps)
    if kind.mixer in ("attn", "attn_local"):
        window = cfg.sliding_window if kind.mixer == "attn_local" else 0
        y, new_cache = attention.prefill_attention(
            params["attn"], h, positions, rope_theta=cfg.rope_theta,
            qk_norm=cfg.qk_norm, cache=cache, window=window,
            q_block=cfg.q_block, kv_block=cfg.kv_block, offset=start_pos)
    elif kind.mixer == "moa":
        b, s, _ = h.shape
        y, new_cache = moa_lib.moa_prefill(
            params["moa"], h, positions, _moa_args(cfg), cache=cache,
            ctx=ctx, mask=_flat_mask(valid, b, s), start_pos=start_pos)
    else:
        if start_pos is not None:
            raise ValueError(
                "chunked prefill requires attention mixers (ssm/hybrid "
                "state scans cannot resume mid-prompt from a cache page)")
        y, new_cache = ssm.mamba(params["mamba"], h, d_state=cfg.ssm_d_state,
                                 return_state=True, ctx=ctx)
    x = x + y
    x, _ = _apply_ffn(params, x, kind, cfg, train=False, rng=None, ctx=ctx,
                      valid=valid)
    return x, new_cache


def block_decode(params, x, kind: LayerKind, cfg: ModelConfig, cache,
                 cur_index,
                 ctx: ctx_lib.MeshContext | None = None, valid=None):
    """One-token decode block. ``cur_index`` is a scalar or a [B] vector of
    per-sequence positions (mixed-age serving slots).  ``valid`` ([B]) is
    slot occupancy — dead slots route nowhere and consume no capacity.
    Returns (x, new_cache, aux)."""
    h = layers.rmsnorm(params["ln1"], x, cfg.norm_eps)
    aux_mix = None
    if kind.mixer in ("attn", "attn_local"):
        window = cfg.sliding_window if kind.mixer == "attn_local" else 0
        y, new_cache = attention.decode_attention(
            params["attn"], h, cache, cur_index,
            rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm, window=window)
    elif kind.mixer == "moa":
        mask = (None if valid is None
                else jnp.asarray(valid, jnp.float32).reshape(-1))
        y, new_cache, a_moa = moa_lib.moa_decode(
            params["moa"], h, cache, cur_index,
            _moa_args(cfg, decode=True), ctx=ctx, mask=mask)
        aux_mix = _moa_aux(a_moa)
    else:
        y, new_cache = ssm.mamba_decode(params["mamba"], h, cache,
                                        d_state=cfg.ssm_d_state)
    x = x + y
    x, aux = _apply_ffn(params, x, kind, cfg, train=False, rng=None, ctx=ctx,
                        valid=valid, decode=True)
    return x, new_cache, _merge_aux(aux_mix, aux)


# ---------------------------------------------------------------------------
# Period-stacked layer stack
# ---------------------------------------------------------------------------

def _stack_tree(tree, n: int):
    """Prepend a stacked 'layers' axis of size n to every ParamDef."""
    def one(d: ParamDef):
        return ParamDef((n,) + d.shape, ("layers",) + d.axes,
                        init=d.init, dtype=d.dtype, fan_in=d.fan_in)
    return jax.tree_util.tree_map(one, tree, is_leaf=pm.is_def)


def stack_defs(cfg: ModelConfig) -> dict:
    kinds = layer_kinds(cfg)
    full, rem = n_periods(cfg)
    defs: dict = {}
    if full:
        defs["periods"] = {
            f"pos{p}": _stack_tree(block_defs(cfg, kinds[p]), full)
            for p in range(cfg.period)}
    if rem:
        defs["tail"] = {f"pos{p}": block_defs(cfg, kinds[p % cfg.period])
                        for p in range(rem)}
    return defs


def stack_apply(params, x, cfg: ModelConfig, *, positions, rng,
                train: bool, ctx: ctx_lib.MeshContext | None = None):
    """Run all layers. Returns (x, summed aux)."""
    kinds = layer_kinds(cfg)
    full, rem = n_periods(cfg)
    aux0 = _zero_aux()

    def period_body(carry, xs):
        x, aux = carry
        period_params, idx = xs
        for p in range(cfg.period):
            sub = (jax.random.fold_in(rng, idx * cfg.period + p)
                   if rng is not None else None)
            x, a = block_apply(period_params[f"pos{p}"], x, kinds[p], cfg,
                               positions=positions, rng=sub, train=train,
                               ctx=ctx)
            if a is not None:
                aux = _add_aux(aux, a)
        return (x, aux), None

    body = jax.checkpoint(period_body) if cfg.remat else period_body
    if full:
        (x, aux0), _ = jax.lax.scan(
            body, (x, aux0),
            (params["periods"], jnp.arange(full)))
    for p in range(rem):
        sub = (jax.random.fold_in(rng, full * cfg.period + p)
               if rng is not None else None)
        x, a = block_apply(params["tail"][f"pos{p}"], x,
                           kinds[p % cfg.period], cfg,
                           positions=positions, rng=sub, train=train,
                           ctx=ctx)
        if a is not None:
            aux0 = _add_aux(aux0, a)
    return x, aux0


def cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Decode-cache ParamDefs matching the stacked parameter structure."""
    kinds = layer_kinds(cfg)
    full, rem = n_periods(cfg)

    def one(kind: LayerKind):
        if kind.mixer in ("attn", "attn_local"):
            window = cfg.sliding_window if kind.mixer == "attn_local" else 0
            return attention.init_cache_defs(
                batch, max_len, cfg.n_kv_heads, cfg.head_dim, window=window,
                dtype=cfg.param_dtype)
        if kind.mixer == "moa":
            # Shared-K/V invariant: an MoA layer's cache is a plain
            # attention cache (pages/prefix reuse work unchanged).
            return moa_lib.init_cache_defs(batch, max_len, _moa_args(cfg),
                                           dtype=cfg.param_dtype)
        return ssm.init_state_defs(batch, cfg.d_model,
                                   d_state=cfg.ssm_d_state,
                                   d_conv=cfg.ssm_d_conv,
                                   expand=cfg.ssm_expand,
                                   dtype=cfg.param_dtype)

    defs: dict = {}
    if full:
        defs["periods"] = {f"pos{p}": _stack_tree(one(kinds[p]), full)
                           for p in range(cfg.period)}
    if rem:
        defs["tail"] = {f"pos{p}": one(kinds[p % cfg.period])
                        for p in range(rem)}
    return defs


def stack_prefill(params, x, cfg: ModelConfig, cache, positions,
                  ctx: ctx_lib.MeshContext | None = None, valid=None,
                  start_pos: int | None = None):
    """Prefill all layers, filling the cache. Returns (x, new_cache).
    ``valid`` ([B, S]) masks padded prompt positions out of MoE routing
    (bucketed prefill).  ``start_pos`` (static int) is the chunked-prefill
    offset: this call ingests prompt positions [start_pos, start_pos + S)
    against a cache already holding [0, start_pos)."""
    kinds = layer_kinds(cfg)
    full, rem = n_periods(cfg)
    new_cache: dict = {}

    def period_body(x, xs):
        period_params, period_cache = xs
        out_cache = {}
        for p in range(cfg.period):
            x, out_cache[f"pos{p}"] = block_prefill(
                period_params[f"pos{p}"], x, kinds[p], cfg,
                period_cache[f"pos{p}"], positions, ctx=ctx, valid=valid,
                start_pos=start_pos)
        return x, out_cache

    body = jax.checkpoint(period_body) if cfg.remat else period_body
    if full:
        x, new_cache["periods"] = jax.lax.scan(
            body, x, (params["periods"], cache["periods"]))
    if rem:
        new_cache["tail"] = {}
        for p in range(rem):
            x, new_cache["tail"][f"pos{p}"] = block_prefill(
                params["tail"][f"pos{p}"], x, kinds[p % cfg.period], cfg,
                cache["tail"][f"pos{p}"], positions, ctx=ctx, valid=valid,
                start_pos=start_pos)
    return x, new_cache


def stack_decode(params, x, cfg: ModelConfig, cache, cur_index,
                 ctx: ctx_lib.MeshContext | None = None, valid=None):
    """One-token decode through all layers.  ``cur_index`` is a scalar or a
    [B] vector of per-sequence positions; ``valid`` ([B]) is slot
    occupancy (dead slots are masked out of MoE routing).  Returns
    (x, new_cache, telemetry) where telemetry is the summed per-expert
    load/overflow counters over MoE layers (None if the model has none)."""
    kinds = layer_kinds(cfg)
    full, rem = n_periods(cfg)
    new_cache: dict = {}
    telem = _telemetry_zero(cfg)

    def period_body(carry, xs):
        x, telem = carry
        period_params, period_cache = xs
        out_cache = {}
        for p in range(cfg.period):
            x, out_cache[f"pos{p}"], aux = block_decode(
                period_params[f"pos{p}"], x, kinds[p], cfg,
                period_cache[f"pos{p}"], cur_index, ctx=ctx, valid=valid)
            telem = _add_telemetry(telem, aux)
        return (x, telem), out_cache

    if full:
        (x, telem), new_cache["periods"] = jax.lax.scan(
            period_body, (x, telem), (params["periods"], cache["periods"]))
    if rem:
        new_cache["tail"] = {}
        for p in range(rem):
            x, new_cache["tail"][f"pos{p}"], aux = block_decode(
                params["tail"][f"pos{p}"], x, kinds[p % cfg.period], cfg,
                cache["tail"][f"pos{p}"], cur_index, ctx=ctx, valid=valid)
            telem = _add_telemetry(telem, aux)
    return x, new_cache, telem
