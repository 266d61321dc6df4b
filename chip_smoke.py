#!/usr/bin/env python3
"""Chip smoke test: serve and train the MoE model on a TPU with the Pallas
kernels compiled, each checked against a reference in the same process.

    python chip_smoke.py             # one chip: serve phase + train phase
    python chip_smoke.py --chips 4   # four chips: only the multi-chip paths

The model is kimi-k2-1t-a32b at its published widths (d_model 7168, 64
query / 8 KV heads of 128, expert width 2048, top-8 swiglu experts,
bf16) with random weights from a fixed seed; depth, expert count and (for
training) the vocabulary are cut so one chip holds it.

One chip:
  serve  2 layers, 16 experts, full 163840 vocab (~8 GB of bf16 weights):
         ``ServeEngine`` serves 8 requests of 128-token prompts x 32 new
         tokens with 8 slots and whole-prompt prefill, on the ``pallas``
         backend and then on ``ref``; the last-prompt-position logits of
         every prompt and one decode step's logits are compared.
  train  1 layer, 8 experts, vocab 20480: ``Trainer`` takes 3 steps at
         batch 2 x 128 with the factored optimizer on each backend; the
         first and the last step's losses are compared.

Four chips (``--chips 4``):
  train  the same train phase on a (data=1, model=4) mesh under
         ``dp_tp_ep``, where the pallas MoE layer runs the all-to-all
         expert-parallel schedule with the experts split 4 ways, against
         the same steps on one of the four chips;
  serve  the serve phase on the 4-chip ``decode_std`` plan against the
         same engine on one chip.
  Each checks from ``memory_stats`` that the model is spread over all
  four chips.

The script fails (non-zero exit, no result line) on any exception, any
kernel fallback, a missing TPU, or a comparison outside its tolerance.
Its last line is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it.  It runs everything in this one process: a process that
touches JAX holds the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
# The TPU runtime writes its logs under /tmp unless told otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ARCH = "kimi-k2-1t-a32b"
SEED = 0
# Cuts of depth, expert count and vocabulary; every width stays published.
SERVE_CUT = dict(n_layers=2, n_experts=16)
TRAIN_CUT = dict(n_layers=1, n_experts=8, vocab_size=20480)
N_REQUESTS, N_SLOTS, PROMPT_LEN, NEW_TOKENS = 8, 8, 128, 32
TRAIN_STEPS, BATCH, SEQ = 3, 2, 128

# Logits of this random-weight model are O(1) (unit-scale normed
# activations into a 1/sqrt(d)-scaled unembedding).  The two paths keep
# bf16 activations and weights and accumulate in f32, but in different
# orders and tilings (Pallas GMM tiles vs XLA dots; a combine sum in the
# kernel vs an XLA gather-sum; sharded vs whole matmuls), so each bf16
# rounding of the residual stream can land one ulp (2^-8 relative) apart
# and two layers of it move a logit by a few 1e-2.  A broken kernel — a
# token in the wrong slot, a dropped or doubled expert output — moves
# logits by O(1).
LOGIT_TOL = 0.25
# The loss is a mean over 256 tokens of a 20480-way cross entropy
# (~ln 20480 = 9.9) plus the balance losses; per-token logit deviations
# of a few 1e-2 average down to ~1e-3.  A wrong expert FFN or combine
# shifts it by > 0.1.
LOSS_TOL = 0.02
# The last step's loss also carries the backward kernels (combine's
# cotangent runs a dispatch copy, dispatch's a combine) through two
# updates.  The factored optimizer's first updates are about ±30 x lr per
# element whatever the gradient's size, so an element whose gradient is
# near zero may step either way on the two paths, but the losses stay
# within a few 1e-4 (1.5e-4 at step 3, pallas vs ref on one v5e); a
# misrouted or dropped expert gradient turns every expert matrix's
# update, and the two updates move the loss by 0.17 in all.
LAST_LOSS_TOL = 0.02


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def compare(name: str, got, want, tol: float) -> None:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"{name}: shape {got.shape} vs "
          f"{want.shape}")
    check(bool(np.isfinite(got).all() and np.isfinite(want).all()),
          f"{name}: non-finite values")
    diff = float(np.max(np.abs(got - want)))
    log(f"compare {name}: max|diff| = {diff!r} (tolerance {tol}, "
        f"max|ref| = {float(np.max(np.abs(want)))!r})")
    check(diff <= tol, f"{name}: max|diff| {diff} > tolerance {tol}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_run(params, cfg, prompts, ctx=None):
    """Serve the prompts through ServeEngine; returns (engine, seconds)."""
    from repro.serve.engine import ServeConfig, ServeEngine
    t0 = time.perf_counter()
    engine = ServeEngine(params, cfg, ServeConfig(
        max_len=PROMPT_LEN + NEW_TOKENS + 1, n_slots=N_SLOTS), ctx=ctx)
    reqs = [engine.submit(p, NEW_TOKENS) for p in prompts]
    engine.run()
    dt = time.perf_counter() - t0
    check(all(r.done and len(r.tokens) == NEW_TOKENS for r in reqs),
          "serve: a request did not complete")
    log(f"serve {cfg.kernel_backend}: {len(reqs)} requests x "
        f"{NEW_TOKENS} tokens, {engine.stats['decode_steps']} decode "
        f"steps in {dt!r} s (compile included)")
    return engine, dt


def serve_logits(engine, prompts, feed=None):
    """Last-prompt-position logits of every prompt, then one decode step
    over all slots fed ``feed`` (default: each prompt's argmax), through
    the engine's own compiled prefill and decode programs."""
    import jax.numpy as jnp
    import numpy as np
    engine.reset()
    n, s = prompts.shape
    first = []
    for slot, prompt in enumerate(prompts):
        logits, page = engine._prefill(
            engine.params, {"tokens": jnp.asarray(prompt[None])},
            engine._blank_page, jnp.asarray(s - 1, jnp.int32),
            jnp.ones((1, s), jnp.float32))
        if engine.ctx.mesh is not None:
            page = engine.decode_ctx.reshard(page, engine.kv.seq_defs)
        engine.kv.insert(slot, page, s)
        first.append(np.asarray(logits[0], np.float32))
    first = np.stack(first)
    if feed is None:
        feed = first.argmax(-1).astype(np.int32)
    logits, _, _ = engine._decode(
        engine.params, jnp.asarray(feed), engine.kv.cache,
        jnp.full((n,), s, jnp.int32), jnp.ones((n,), jnp.float32))
    return first, np.asarray(logits, np.float32), feed


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_run(params, cfg, name, ctx=None):
    """TRAIN_STEPS steps of Trainer; returns (trainer, losses)."""
    import math

    from repro.data.pipeline import DataConfig, DataIterator
    from repro.models import lm
    from repro.optim.optimizers import OptConfig
    from repro.train.trainer import Trainer, TrainLoopConfig
    workdir = os.path.join(ROOT, ".smoke_work", name)
    shutil.rmtree(workdir, ignore_errors=True)
    t0 = time.perf_counter()
    trainer = Trainer(
        loss_fn=lambda p, b, r: lm.lm_loss(p, b, cfg, rng=r, ctx=ctx),
        params=params,
        oc=OptConfig(kind="factored", learning_rate=1e-4,
                     warmup_steps=10),
        loop=TrainLoopConfig(total_steps=TRAIN_STEPS,
                             checkpoint_every=10 ** 9, log_every=1),
        data_iter=DataIterator(DataConfig(
            vocab_size=cfg.vocab_size, seq_len=SEQ, batch_size=BATCH,
            n_clusters=64, seed=SEED)),
        workdir=workdir, ctx=ctx, kernel_backend=cfg.kernel_backend,
        router=cfg.router)
    trainer.run()
    dt = time.perf_counter() - t0
    losses = [m["loss"] for m in trainer.metrics_log]
    check(len(losses) == TRAIN_STEPS and all(map(math.isfinite, losses)),
          f"train {name}: losses {losses}")
    log(f"train {name}: {TRAIN_STEPS} steps of {BATCH}x{SEQ} tokens in "
        f"{dt!r} s (compile and checkpoint included), losses {losses}")
    return trainer, losses


def _free_workdir():
    shutil.rmtree(os.path.join(ROOT, ".smoke_work"), ignore_errors=True)


def materialize(cfg, ctx=None):
    """Random weights from SEED, generated on the device(s) in one
    program (sharded over ctx's mesh when given).

    The router's gate matrices are drawn too, N(0, 1/d_model).  The model
    initialises them to zero (the paper's Appendix A, for the start of
    training), which makes every routing score tie: every token would go
    to the same k lowest-numbered experts, overflowing their capacity and
    leaving the others idle.  A random gate spreads tokens over all the
    experts, as a trained router does."""
    import math

    import jax

    from repro.common import param as pm
    from repro.models import lm
    defs = lm.lm_defs(cfg)

    def init(key):
        leaves, tree = jax.tree_util.tree_flatten_with_path(
            pm.materialize(defs, key))
        gate_key = jax.random.fold_in(key, 1)
        return jax.tree_util.tree_unflatten(tree, [
            jax.random.normal(jax.random.fold_in(gate_key, i), x.shape,
                              x.dtype) / math.sqrt(cfg.d_model)
            if jax.tree_util.keystr(path).endswith("['wg']") else x
            for i, (path, x) in enumerate(leaves)])

    out = None if ctx is None else ctx.tree_shardings(defs)
    return jax.jit(init, out_shardings=out)(jax.random.PRNGKey(SEED))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def one_chip(base):
    import numpy as np

    from repro.common import param as pm

    # serve: pallas, then ref on the same weights and prompts
    t0 = time.perf_counter()
    cfg = base.replace(**SERVE_CUT)
    params = materialize(cfg)
    log(f"serve model: {pm.param_count(params) / 1e9!r} B params "
        f"({pm.param_bytes(params) / 1e9!r} GB)")
    prompts = np.random.RandomState(SEED).randint(
        1, cfg.vocab_size, (N_REQUESTS, PROMPT_LEN)).astype(np.int32)
    got = {}
    for backend in ("pallas", "ref"):
        c = cfg.replace(kernel_backend=backend)
        engine, _ = serve_run(params, c, prompts)
        feed = got["pallas"][2] if backend == "ref" else None
        got[backend] = serve_logits(engine, prompts, feed)
        del engine
    compare("serve prefill logits (pallas vs ref)", got["pallas"][0],
            got["ref"][0], LOGIT_TOL)
    compare("serve decode logits (pallas vs ref)", got["pallas"][1],
            got["ref"][1], LOGIT_TOL)
    del params, got
    log(f"phase serve: {time.perf_counter() - t0!r} s")

    # train: fresh weights per run (the trainer donates its state)
    t0 = time.perf_counter()
    cfg = base.replace(**TRAIN_CUT)
    losses = {}
    for backend in ("pallas", "ref"):
        params = materialize(cfg)
        trainer, losses[backend] = train_run(
            params, cfg.replace(kernel_backend=backend), backend)
        del trainer, params
    _free_workdir()
    compare("train first-step loss (pallas vs ref)", losses["pallas"][0],
            losses["ref"][0], LOSS_TOL)
    compare("train last-step loss (pallas vs ref)", losses["pallas"][-1],
            losses["ref"][-1], LAST_LOSS_TOL)
    log(f"phase train: {time.perf_counter() - t0!r} s")


def _check_spread(tag: str, model_bytes: int) -> None:
    """Every chip must hold at least half its even share of the model."""
    import jax
    in_use = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
    log(f"{tag} bytes_in_use per device: {in_use} (model "
        f"{model_bytes} B)")
    share = model_bytes / len(in_use)
    check(min(in_use) >= share / 2,
          f"{tag}: the model is not spread over the devices: {in_use}")


def four_chips(base):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from repro.common import param as pm
    from repro.core.router import RouterSpec
    from repro.launch.mesh import make_host_mesh
    from repro.sharding import context as ctx_lib

    mesh = make_host_mesh(model=4)
    log(f"mesh: {dict(mesh.shape)}")
    one = SingleDeviceSharding(jax.devices()[0])
    copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))

    def on_one(tree):
        """A fresh copy on the first chip: a program output shares no
        buffer with the sharded original, which the trainer donates."""
        return copy(jax.device_put(tree, one))

    # train: the expert-parallel schedule routes each shard's tokens with
    # its own noise draw, so routing noise is off to make the 4-chip and
    # one-chip steps the same computation.  (k = E = 8 and capacity
    # factor 1.25: no token is dropped on either path.)
    t0 = time.perf_counter()
    cfg = base.replace(**TRAIN_CUT, kernel_backend="pallas",
                       router=RouterSpec(capacity_factor=1.25, noise=False))
    ctx = ctx_lib.MeshContext.for_mesh(mesh, "dp_tp_ep")
    params = materialize(cfg, ctx)
    params_one = on_one(params)
    model_bytes = pm.param_bytes(params)
    trainer, losses4 = train_run(params, cfg, "4chip", ctx=ctx)
    _check_spread("train 4-chip", model_bytes)
    batch = {k: np.asarray(v) for k, v in
             next(trainer.data_iter).items()}
    hlo = trainer.step_fn.lower(trainer.state, batch,
                                jax.random.PRNGKey(0)).as_text()
    check("all_to_all" in hlo, "train 4-chip: no all_to_all in the step")
    log("train 4-chip step runs the all_to_all expert-parallel schedule")
    del trainer, params
    trainer, losses1 = train_run(params_one, cfg, "1chip")
    del trainer, params_one
    _free_workdir()
    compare("train first-step loss (4 chips vs 1)", losses4[0], losses1[0],
            LOSS_TOL)
    compare("train last-step loss (4 chips vs 1)", losses4[-1], losses1[-1],
            LAST_LOSS_TOL)
    log(f"phase train 4-chip: {time.perf_counter() - t0!r} s")

    # serve on the decode_std plan
    t0 = time.perf_counter()
    cfg = base.replace(**SERVE_CUT, kernel_backend="pallas")
    ctx = ctx_lib.MeshContext.for_mesh(mesh, "decode_std")
    params = materialize(cfg, ctx)
    model_bytes = pm.param_bytes(params)
    prompts = np.random.RandomState(SEED).randint(
        1, cfg.vocab_size, (N_REQUESTS, PROMPT_LEN)).astype(np.int32)
    engine, _ = serve_run(params, cfg, prompts, ctx=ctx)
    _check_spread("serve 4-chip", model_bytes)
    got4 = serve_logits(engine, prompts)
    del engine
    # The engine donates nothing, so the gathered tree may share buffers
    # with the sharded one; a copy would not fit next to it on one chip.
    params_one = jax.device_put(params, one)
    del params
    engine, _ = serve_run(params_one, cfg, prompts)
    got1 = serve_logits(engine, prompts, got4[2])
    del engine, params_one
    compare("serve prefill logits (4 chips vs 1)", got4[0], got1[0],
            LOGIT_TOL)
    compare("serve decode logits (4 chips vs 1)", got4[1], got1[1],
            LOGIT_TOL)
    log(f"phase serve 4-chip: {time.perf_counter() - t0!r} s")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        log("no TPU found; this smoke test runs on the chip only")
        return 1
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, found "
            f"{len(devices)}")
        return 1

    from repro.common.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    from repro.configs.base import get_config
    from repro.kernels import backend as backend_lib
    from repro.kernels import gmm as gmm_lib

    base = get_config(ARCH)
    log(f"model {ARCH}: d_model={base.d_model} heads={base.n_heads}/"
        f"{base.n_kv_heads}x{base.head_dim} expert_width={base.moe_d_ff} "
        f"top-{base.moe_k} {base.activation} "
        f"{jax.numpy.dtype(base.param_dtype).name}")
    for phase, cut in (("serve", SERVE_CUT), ("train", TRAIN_CUT)):
        log(f"cut for {phase}: " + ", ".join(
            f"{k} {getattr(base, k)} -> {v}" for k, v in cut.items()))
    log("kernel backend: pallas (compared with ref)" if args.chips == 1
        else "kernel backend: pallas (4 chips compared with 1)")

    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            one_chip(base)
        else:
            four_chips(base)
    except SmokeFailure as err:
        log(f"FAILED: {err}")
        return 1
    finally:
        _free_workdir()
    fallbacks = backend_lib.fallbacks()
    log(f"kernel fallbacks: {sum(fallbacks.values())} {fallbacks}")
    log(f"GMM plans by how their tiles were resolved: "
        f"{gmm_lib.plan_sources()}")
    if fallbacks:
        log("FAILED: a kernel call fell back off the pallas kernels")
        return 1
    log(f"total: {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
