"""Training launcher: ``--arch <id>`` selects any zoo architecture.

On a real TPU slice this runs under ``jax.distributed.initialize()`` with
the production mesh; on a dev host it uses whatever devices exist and a
reduced config unless ``--full`` is passed.  Fault tolerance is on by
default: atomic checkpoints every ``--checkpoint-every`` steps, auto-resume
from the newest one, straggler events logged to the heartbeat file.

Example:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 50 --reduce --workdir /tmp/run1
"""
from __future__ import annotations

import argparse
import contextlib
import os
import tempfile

import jax
import jax.numpy as jnp

from repro.common import param as pm
from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import get_config
from repro.core import router as router_lib
from repro.data.pipeline import DataConfig, DataIterator
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.optim.optimizers import OptConfig
from repro.sharding import context as ctx_lib
from repro.train.trainer import Trainer, TrainLoopConfig


def reduced(cfg):
    kw = dict(n_layers=(2 * cfg.period) if cfg.period > 1 else 2,
              d_model=64, vocab_size=512, param_dtype=jnp.float32,
              compute_dtype=jnp.float32, q_block=32, kv_block=32)
    if cfg.n_heads:
        kw.update(n_heads=4, n_kv_heads=2, head_dim=16)
    if cfg.d_ff:
        kw.update(d_ff=128)
    if cfg.n_experts:
        kw.update(n_experts=8, moe_k=2, moe_d_ff=64)
    if cfg.moa_experts:
        kw.update(moa_experts=4, moa_k=2, moa_heads_per_expert=2)
    if cfg.ssm_d_state:
        kw.update(ssm_d_state=4)
    if cfg.sliding_window:
        kw.update(sliding_window=32)
    if cfg.n_prefix:
        kw.update(n_prefix=0, frontend="none")
    return cfg.replace(**kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--optimizer", default="factored",
                    choices=["factored", "adam"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--reduce", action="store_true",
                    help="shrink the config for a dev host")
    ap.add_argument("--kernel-backend", default=None,
                    choices=["ref", "pallas"],
                    help="MoE kernel backend override (docs/kernels.md); "
                         "default: the arch config's choice")
    ap.add_argument("--dispatch-vmem-limit", type=int, default=None,
                    help="VMEM budget (bytes) for the fused dispatch/"
                         "combine kernels; past it the pallas backend "
                         "E-blocks the [E, C, d] buffer")
    ap.add_argument("--dispatch-e-block", type=int, default=None,
                    help="force the fused dispatch/combine expert-slab "
                         "size; default: auto-select against the budget")
    ap.add_argument("--no-gmm-autotune", action="store_true",
                    help="ignore the GMM tiling table and the tile "
                         "rule and pin static 128 tiles")
    ap.add_argument("--router-policy", default=None,
                    help="routing policy override (docs/routing.md): "
                         "noisy_topk | batchwise | threshold | "
                         "expert_choice | any registered policy")
    ap.add_argument("--capacity-factor", type=float, default=None,
                    help="train capacity-factor override (RouterSpec)")
    ap.add_argument("--eval-capacity-factor", type=float, default=None,
                    help="eval capacity-factor override (RouterSpec)")
    ap.add_argument("--moa-k", type=int, default=None,
                    help="MoA head-groups-per-token override (archs with "
                         "moa_positions; docs/moa.md)")
    ap.add_argument("--workdir", default=os.path.join(
        tempfile.gettempdir(), "repro_train"))
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="write one profiler trace of the run under DIR: "
                         "device ops and the train.* spans on one clock "
                         "(.xplane.pb, and perfetto_trace.json.gz for "
                         "Perfetto; docs/observability.md)")
    args = ap.parse_args()
    print(f"[train] compile cache: {enable_compile_cache()}")

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    if args.kernel_backend is not None:
        cfg = cfg.replace(kernel_backend=args.kernel_backend)
    if args.dispatch_vmem_limit is not None:
        cfg = cfg.replace(dispatch_vmem_limit=args.dispatch_vmem_limit)
    if args.dispatch_e_block is not None:
        cfg = cfg.replace(dispatch_e_block=args.dispatch_e_block)
    if args.no_gmm_autotune:
        cfg = cfg.replace(gmm_autotune=False)
    # Router flags configure the spec at ONE resolution point: whatever
    # the arch config carries (explicit spec or legacy fields) resolves to
    # a RouterSpec here, the overrides land on it, and the spec rides
    # cfg.router through every MoE layer (docs/routing.md).
    if (args.router_policy is not None or args.capacity_factor is not None
            or args.eval_capacity_factor is not None):
        spec = router_lib.resolve_spec(cfg)
        if args.router_policy is not None:
            spec = spec.replace(policy=args.router_policy)
        if args.capacity_factor is not None:
            spec = spec.replace(capacity_factor=args.capacity_factor)
        if args.eval_capacity_factor is not None:
            spec = spec.replace(eval_capacity_factor=
                                args.eval_capacity_factor)
        router_lib.get_policy(spec.policy)   # unknown policy fails here
        cfg = cfg.replace(router=spec)
        print(f"[train] router: {spec}")
    if args.moa_k is not None:
        if not cfg.moa_positions:
            raise SystemExit(
                f"--moa-k: arch {cfg.name!r} has no MoA layers "
                "(moa_positions is empty)")
        cfg = cfg.replace(moa_k=args.moa_k)
        print(f"[train] moa_k: {cfg.moa_k}/{cfg.moa_experts} head groups")
    params = pm.materialize(lm.lm_defs(cfg), jax.random.PRNGKey(0))
    print(f"[train] {cfg.name}: {pm.param_count(params)/1e6:.1f}M params "
          f"on {len(jax.devices())} device(s)")

    # Explicit sharding context: a host mesh when more than one device is
    # visible, else the null (identity-constraint) context.
    if len(jax.devices()) > 1:
        ctx = ctx_lib.MeshContext.for_mesh(make_host_mesh(), "dp_tp_ep")
    else:
        ctx = ctx_lib.MeshContext.null()

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    batch_size=args.batch, n_clusters=64)
    trainer = Trainer(
        loss_fn=lambda p, b, r: lm.lm_loss(p, b, cfg, rng=r, ctx=ctx),
        params=params,
        oc=OptConfig(kind=args.optimizer, learning_rate=args.lr,
                     warmup_steps=max(args.steps // 10, 10)),
        loop=TrainLoopConfig(total_steps=args.steps,
                             microbatches=args.microbatches,
                             checkpoint_every=args.checkpoint_every,
                             log_every=10),
        data_iter=DataIterator(dc), workdir=args.workdir,
        kernel_backend=cfg.kernel_backend, router=cfg.router)
    with (jax.profiler.trace(args.trace, create_perfetto_trace=True)
          if args.trace else contextlib.nullcontext()):
        final = trainer.run()
    if args.trace:
        print(f"[train] profiler trace written under {args.trace} "
              "(perfetto_trace.json.gz loads in Perfetto)")
    print(f"[train] done: {final}")


if __name__ == "__main__":
    main()
