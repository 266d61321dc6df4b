"""Training loop: microbatched step builder + fault-tolerant driver.

``make_train_step`` builds the jitted SPMD step:

* gradient accumulation over ``microbatches`` via ``lax.scan`` (the grad
  tree is the carry, so activation memory is one microbatch's worth — how
  train_4k's 1M-token global batches fit);
* loss = token xent + the paper's §4 balancing losses (already summed into
  the model loss);
* global-norm clipping + Adam/factored update (optim/optimizers.py).

``Trainer`` is the fault-tolerance harness:

* auto-restore from the newest complete checkpoint (params, optimizer,
  data-iterator step) — a killed job resumes bit-exact (tested);
* async checkpoint every ``checkpoint_every`` steps;
* heartbeat file + step-time tracking: steps slower than
  ``straggler_factor`` × running median are logged as straggler events
  (the launcher's watchdog restarts/re-meshes on repeated events);
* optional crash injection for the fault-tolerance tests;
* spans: ``train.step`` around each step (which waits for its loss),
  ``train.data`` and ``train.checkpoint`` around input and save work,
  with the trainer's tracer installed as the ambient one.  They are
  profiler annotations, so under ``jax.profiler.trace`` they share the
  device trace's clock; with ``trace_path`` set they are also kept as
  a chrome trace (docs/observability.md).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as trace_lib
from repro.optim import optimizers as opt_lib
from repro.sharding import context as ctx_lib
from repro.train.checkpoint import CheckpointManager


@dataclasses.dataclass(frozen=True)
class TrainLoopConfig:
    total_steps: int = 100
    microbatches: int = 1
    checkpoint_every: int = 50
    keep_checkpoints: int = 3
    log_every: int = 10
    straggler_factor: float = 3.0
    seed: int = 0


def _split_microbatches(batch: dict, n: int) -> dict:
    def reshape(x):
        b = x.shape[0]
        if b % n != 0:
            raise ValueError(
                f"batch size {b} not divisible into {n} microbatches")
        return x.reshape((n, b // n) + x.shape[1:])
    return jax.tree_util.tree_map(reshape, batch)


def make_train_step(loss_fn: Callable, oc: opt_lib.OptConfig, *,
                    microbatches: int = 1):
    """loss_fn(params, batch, rng) -> (loss, metrics dict of scalars)."""

    def step(state, batch, rng):
        params = state["params"]

        def compute(params, mb, r):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb, r)
            return grads, metrics

        if microbatches > 1:
            mbs = _split_microbatches(batch, microbatches)
            rngs = jax.random.split(rng, microbatches)

            def body(carry, xs):
                acc, met_acc = carry
                mb, r = xs
                grads, metrics = compute(params, mb, r)
                acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                met_acc = jax.tree_util.tree_map(jnp.add, met_acc, metrics)
                return (acc, met_acc), None

            zeros_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            mb0 = jax.tree_util.tree_map(lambda x: x[0], mbs)
            _, met0 = jax.eval_shape(lambda: compute(params, mb0, rngs[0]))
            zeros_m = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), met0)
            (grads, metrics), _ = jax.lax.scan(body, (zeros_g, zeros_m),
                                               (mbs, rngs))
            grads = jax.tree_util.tree_map(lambda g: g / microbatches,
                                           grads)
            metrics = jax.tree_util.tree_map(lambda m: m / microbatches,
                                             metrics)
        else:
            grads, metrics = compute(params, batch, rng)

        new_params, new_opt, info = opt_lib.apply_updates(
            params, grads, state["opt"], oc)
        metrics = dict(metrics, **info)
        return {"params": new_params, "opt": new_opt}, metrics

    return step


class Trainer:
    def __init__(self, *, loss_fn, params, oc: opt_lib.OptConfig,
                 loop: TrainLoopConfig, data_iter, workdir: str,
                 jit: bool = True, crash_at_step: int | None = None,
                 ctx: ctx_lib.MeshContext | None = None,
                 kernel_backend: str | None = None,
                 router=None, trace_path: str | None = None):
        # The sharding context is entered around step tracing so loss
        # closures that consult current_ctx() (instead of binding ctx
        # explicitly) still resolve the right mesh/plan.
        self.ctx = ctx
        # Fail-fast *validation* of the kernel backend the model config is
        # expected to use: raises KernelBackendError at construction
        # instead of mid-trace at the first jitted step.  Selection itself
        # lives in the loss closure's MoEArgs/ModelConfig — this argument
        # does not override it.
        self.kernel_backend = kernel_backend
        if kernel_backend is not None:
            from repro.kernels import backend as backend_lib
            backend_lib.get(kernel_backend)
            print(f"[trainer] kernel backend {kernel_backend!r} validated "
                  "(active backend is set by the model config)")
        # Same fail-fast validation for the RouterSpec the model config is
        # expected to route with: an unknown policy raises RouterError at
        # construction, not mid-trace (docs/routing.md).
        self.router = router
        if router is not None:
            from repro.core import router as router_lib
            router_lib.get_policy(router.policy)
            print(f"[trainer] router policy {router.policy!r} validated "
                  "(active spec is set by the model config)")
        self.loop = loop
        self.data_iter = data_iter
        self.workdir = workdir
        os.makedirs(workdir, exist_ok=True)
        self.ckpt = CheckpointManager(os.path.join(workdir, "ckpt"),
                                      keep=loop.keep_checkpoints)
        self.state = {"params": params, "opt": opt_lib.init(params, oc)}
        step_fn = make_train_step(loss_fn, oc,
                                  microbatches=loop.microbatches)
        self.step_fn = jax.jit(step_fn, donate_argnums=(0,)) if jit \
            else step_fn
        self.start_step = 0
        self.crash_at_step = crash_at_step
        # Chrome-trace capture (docs/observability.md): None => the shared
        # null tracer (each span site costs one no-op context manager).
        self.tracer = (trace_lib.Tracer(trace_path, process_name="train")
                       if trace_path else trace_lib.NULL)
        self.metrics_log: list[dict] = []
        self._durations: list[float] = []
        self.straggler_events: list[dict] = []
        self._maybe_restore()

    # -- fault tolerance --------------------------------------------------
    def _maybe_restore(self):
        latest = self.ckpt.latest_step()
        if latest is None:
            return
        self.state, extra, step = self.ckpt.restore(latest, self.state)
        self.start_step = step
        self.data_iter.restore(extra["data"])
        print(f"[trainer] restored checkpoint at step {step}")

    def _heartbeat(self, step: int):
        with open(os.path.join(self.workdir, "heartbeat.json"), "w") as f:
            json.dump({"step": step, "time": time.time()}, f)

    def _check_straggler(self, step: int, dt: float):
        self._durations.append(dt)
        if len(self._durations) >= 8:
            med = float(np.median(self._durations[-32:]))
            if dt > self.loop.straggler_factor * med:
                ev = {"step": step, "duration": dt, "median": med}
                self.straggler_events.append(ev)
                print(f"[trainer] STRAGGLER step {step}: {dt:.3f}s vs "
                      f"median {med:.3f}s")

    # -- main loop ---------------------------------------------------------
    def run(self) -> dict:
        rng = jax.random.PRNGKey(self.loop.seed)
        last_metrics = {}
        for step in range(self.start_step, self.loop.total_steps):
            if self.crash_at_step is not None and step == self.crash_at_step:
                # Test hook: let any in-flight async checkpoint complete so
                # the crash point is deterministic (a real SIGKILL may lose
                # the newest checkpoint; restore falls back to the previous
                # complete one either way).
                self.ckpt.wait()
                raise RuntimeError(f"injected crash at step {step}")
            tr = self.tracer
            with tr.span("train.data", step=step):
                batch = next(self.data_iter)
            t0 = time.perf_counter()
            with trace_lib.use(tr), \
                    tr.span("train.step", step=step,
                            microbatches=self.loop.microbatches), \
                    (self.ctx if self.ctx is not None
                     else ctx_lib.MeshContext.null()):
                self.state, metrics = self.step_fn(
                    self.state, batch, jax.random.fold_in(rng, step))
                jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            self._heartbeat(step)
            self._check_straggler(step, dt)
            if (step + 1) % self.loop.log_every == 0 or \
                    step == self.loop.total_steps - 1:
                last_metrics = {k: float(v) for k, v in metrics.items()}
                last_metrics["step"] = step + 1
                last_metrics["step_time_s"] = dt
                self.metrics_log.append(last_metrics)
                print(f"[trainer] step {step+1} "
                      f"loss={last_metrics.get('loss', float('nan')):.4f} "
                      f"({dt:.3f}s)")
            if (step + 1) % self.loop.checkpoint_every == 0:
                with self.tracer.span("train.checkpoint", step=step + 1):
                    self.ckpt.save_async(step + 1, self.state,
                                         {"data": self.data_iter.state()})
        self.ckpt.wait()
        self.ckpt.save(self.loop.total_steps, self.state,
                       {"data": self.data_iter.state()})
        if self.tracer.enabled and self.tracer.path:
            self.tracer.save()
        with open(os.path.join(self.workdir, "metrics.jsonl"), "a") as f:
            for m in self.metrics_log:
                f.write(json.dumps(m) + "\n")
        return last_metrics
