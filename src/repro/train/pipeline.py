"""SPMD pipeline parallelism (GPipe schedule) for the layer stack.

Why: the §Roofline analysis shows trillion-parameter MoE training on a 2D
(data x model) mesh is *structurally* collective-bound — expert weights
(~2 TB for kimi-k2) must either be re-gathered every microbatch (ZeRO-3:
~117 s/step of wire) or their partial sums reduced every microbatch
(expert-TP: ~43 s/step).  Pipelining is the fix the paper's scale demands:
each stage *owns* its layers' weights — zero weight motion — and the only
steady-state communication is the microbatch activation boundary
([tokens_mb, d], ~58 MB for kimi) plus the in-stage EP all-to-all.

Construction (validated fwd+bwd against the sequential stack in
tests/test_pipeline.py):

* mesh axes: the ``data`` axis becomes the ``stage`` ring; ``model`` stays
  tensor/expert-parallel *inside* each stage (the context.shard_map wrapper
  is manual over the stage axis only; GSPMD keeps handling the model axis
  within the stage body, and the stage body's MeshContext records the stage
  axis as Manual so layer constraints strip it).
* layers: stacked [n_stages, layers_per_stage, ...] with the leading dim
  sharded over the stage axis.  Ragged depths (kimi's 61 layers on 16
  stages) pad to the next multiple with *identity* layers — zero output
  projections make a residual block exactly the identity; the padding
  overhead is reported, not hidden.
* schedule: T = n_micro + n_stages - 1 ticks under ``lax.scan``; each tick
  every stage runs one microbatch (bubble ticks compute garbage that is
  masked out — the classic GPipe bubble, fraction (S-1)/T).
* backward: plain ``jax.grad`` through the scan — ``ppermute``'s transpose
  is the reverse shift, so the backward pipeline emerges from autodiff.
  ``jax.checkpoint`` on the stage body keeps the stash at one activation
  boundary per tick.

Embedding and the chunked cross-entropy stay outside the pipelined region
(they are vocab-sharded over the model axis as usual); boundary activations
enter/exit via a masked psum over the stage axis once per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common import param as pm
from repro.configs.base import ModelConfig, layer_kinds
from repro.models import lm, layers, transformer
from repro.optim import optimizers as opt_lib
from repro.sharding import context as ctx_lib


def stages_for(cfg: ModelConfig, n_stages: int) -> tuple[int, int]:
    """(layers_per_stage, padded_total)."""
    per = -(-cfg.n_layers // n_stages)
    return per, per * n_stages


def pipeline_block_defs(cfg: ModelConfig, n_stages: int) -> dict:
    """Stacked [n_stages, layers_per_stage, ...] block params.

    Only homogeneous (period=1) stacks are pipelined here; patterned archs
    would stage at period granularity (not needed for the hillclimb cells).
    """
    if cfg.period != 1:
        raise ValueError(
            f"pipeline stages require homogeneous layers (period=1), got "
            f"period={cfg.period}")
    per, total = stages_for(cfg, n_stages)
    kind = layer_kinds(cfg)[0]
    one = transformer.block_defs(cfg, kind)

    def stack(d: pm.ParamDef):
        return pm.ParamDef((n_stages, per) + d.shape,
                           ("stage", "layers") + d.axes,
                           init=d.init, dtype=d.dtype, fan_in=d.fan_in)
    return jax.tree_util.tree_map(stack, one, is_leaf=pm.is_def)


def zero_identity_padding(params, cfg: ModelConfig, n_stages: int):
    """Zero the output projections of padding layers so they become exact
    identities (residual + zero update)."""
    per, total = stages_for(cfg, n_stages)
    n_pad = total - cfg.n_layers

    def mask_layer(leaf, name_has_out: bool):
        if n_pad == 0 or not name_has_out:
            return leaf
        flat = leaf.reshape((total,) + leaf.shape[2:])
        flat = flat.at[cfg.n_layers:].set(0)
        return flat.reshape(leaf.shape)

    out = dict(params)
    if "attn" in params:
        out["attn"] = dict(params["attn"])
        out["attn"]["wo"] = mask_layer(params["attn"]["wo"], True)
    if "moe" in params:
        out["moe"] = dict(params["moe"])
        out["moe"]["w2"] = mask_layer(params["moe"]["w2"], True)
    if "mlp" in params:
        out["mlp"] = dict(params["mlp"])
        out["mlp"]["w2"] = mask_layer(params["mlp"]["w2"], True)
    if "mamba" in params:
        out["mamba"] = dict(params["mamba"])
        out["mamba"]["out_proj"] = mask_layer(params["mamba"]["out_proj"],
                                              True)
    return out


def pipeline_stack_apply(block_params, x_mb, cfg: ModelConfig, *,
                         mesh, n_stages: int, stage_axis: str = "data",
                         positions, rng, train: bool = True,
                         ctx: ctx_lib.MeshContext | None = None):
    """Run the pipelined layer stack.

    block_params: stacked [S, per, ...] tree (leading dim sharded over the
    stage axis).  x_mb: [n_micro, B_mb, S_seq, d].  Returns
    (y_mb [n_micro, B_mb, S_seq, d], aux_loss scalar).

    The stage body runs under a derived context that records the stage
    axis as Manual — layer-internal constraints strip it automatically
    (no runtime mesh reflection).
    """
    n_micro = x_mb.shape[0]
    kind = layer_kinds(cfg)[0]
    per = stages_for(cfg, n_stages)[0]
    ctx = ctx or ctx_lib.MeshContext.for_mesh(mesh)
    stage_ctx = ctx.manual(stage_axis)

    def stage_body(params_stage, x, mb_rng):
        # params_stage: [per, ...] one stage's layers; x: [B_mb, S, d]
        # aux is carried rank-1 ([1]) through the layer scan and the
        # stage boundary; the caller reads element 0.
        aux = jnp.zeros((1,), jnp.float32)

        def layer_step(carry, xs):
            x, aux = carry
            p_layer, i = xs
            sub = (jax.random.fold_in(mb_rng, i) if mb_rng is not None
                   else None)
            x, a = transformer.block_apply(p_layer, x, kind, cfg,
                                           positions=positions, rng=sub,
                                           train=train, ctx=stage_ctx)
            if a is not None:
                aux = aux + a["aux_loss"]
            return (x, aux), None

        body = jax.checkpoint(layer_step) if cfg.remat else layer_step
        (x, aux), _ = jax.lax.scan(body, (x, aux),
                                   (params_stage, jnp.arange(per)))
        return x, aux

    def per_stage(params_local, xs_all):
        sid = jax.lax.axis_index(stage_axis)
        state = jnp.zeros_like(xs_all[0])
        outputs = jnp.zeros_like(xs_all)
        aux_total = jnp.zeros((1,), jnp.float32)
        t_total = n_micro + n_stages - 1

        def tick(carry, t):
            state, outputs, aux_total = carry
            recv = jax.lax.ppermute(
                state, stage_axis,
                [(i, i + 1) for i in range(n_stages - 1)])
            x_in = jnp.where(sid == 0,
                             xs_all[jnp.clip(t, 0, n_micro - 1)], recv)
            mb = jnp.clip(t - sid, 0, n_micro - 1)
            rng_t = (jax.random.fold_in(rng, mb * n_stages + sid)
                     if rng is not None else None)
            y, aux = stage_body(
                jax.tree_util.tree_map(lambda p: p[0], params_local),
                x_in, rng_t)
            live = (t - sid >= 0) & (t - sid < n_micro)
            aux_total = aux_total + jnp.where(live, aux, 0.0)
            out_mb = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            write = (sid == n_stages - 1) & (t >= n_stages - 1)
            outputs = outputs.at[out_mb].set(
                jnp.where(write, y, outputs[out_mb]))
            return (y, outputs, aux_total), None

        (state, outputs, aux_total), _ = jax.lax.scan(
            tick, (state, outputs, aux_total), jnp.arange(t_total))
        outputs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outputs, 0.0), stage_axis)
        # per-microbatch balance losses averaged over microbatches (same
        # normalization as the grad-accumulation trainer); rank-1, see
        # note above.
        aux_total = jax.lax.psum(aux_total, stage_axis) / n_micro
        return outputs, aux_total

    from jax.sharding import PartitionSpec as P
    fn = ctx_lib.shard_map(
        per_stage, mesh,
        (P(stage_axis), P()),
        (P(), P()),
        manual_axes=(stage_axis,))
    y_mb, aux = fn(block_params, x_mb)
    return y_mb, aux[0]


def pipeline_lm_loss(params, batch, cfg: ModelConfig, *, mesh,
                     n_stages: int, n_micro: int,
                     stage_axis: str = "data", rng=None,
                     train: bool = True,
                     ctx: ctx_lib.MeshContext | None = None):
    """Full LM loss with the block stack pipelined.

    params: {"embed", "blocks" (stacked pipeline defs), "ln_f", "unembed"}.
    batch tokens: [B, S]; B must divide into n_micro microbatches.
    """
    tokens, labels = batch["tokens"], batch["labels"]
    b, s = tokens.shape
    if b % n_micro != 0:
        raise ValueError(
            f"batch size {b} not divisible into {n_micro} microbatches")
    x = layers.embed(params["embed"], tokens, cfg.compute_dtype)
    x_mb = x.reshape(n_micro, b // n_micro, s, -1)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b // n_micro, s))
    y_mb, aux = pipeline_stack_apply(
        params["blocks"], x_mb, cfg, mesh=mesh, n_stages=n_stages,
        stage_axis=stage_axis, positions=positions, rng=rng, train=train,
        ctx=ctx)
    y = y_mb.reshape(b, s, -1)
    y = layers.rmsnorm(params["ln_f"], y, cfg.norm_eps)
    xent = lm.chunked_xent(params, y, labels, cfg,
                           chunk=min(512, s), ctx=ctx)
    loss = xent + aux
    return loss, {"xent": xent, "aux_loss": aux, "loss": loss}


def pipeline_param_defs(cfg: ModelConfig, n_stages: int) -> dict:
    return {
        "embed": layers.embed_defs(cfg.vocab_size, cfg.d_model,
                                   cfg.param_dtype),
        "blocks": pipeline_block_defs(cfg, n_stages),
        "ln_f": layers.rmsnorm_defs(cfg.d_model),
        "unembed": {"w": pm.ParamDef((cfg.d_model, cfg.vocab_size),
                                     ("embed_fsdp", "vocab"),
                                     dtype=cfg.param_dtype,
                                     fan_in=cfg.d_model)},
    }


def make_pipeline_train_step(cfg: ModelConfig, oc: opt_lib.OptConfig, *,
                             mesh, n_stages: int, n_micro: int,
                             stage_axis: str = "data",
                             ctx: ctx_lib.MeshContext | None = None):
    ctx = ctx or ctx_lib.MeshContext.for_mesh(mesh)

    def loss_fn(params, batch, rng):
        return pipeline_lm_loss(params, batch, cfg, mesh=mesh,
                                n_stages=n_stages, n_micro=n_micro,
                                stage_axis=stage_axis, rng=rng, ctx=ctx)

    def train_step(state, batch, seed):
        rng = jax.random.PRNGKey(seed)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"], batch, rng)
        new_params, new_opt, info = opt_lib.apply_updates(
            state["params"], grads, state["opt"], oc)
        return {"params": new_params, "opt": new_opt}, dict(metrics, **info)

    return train_step
