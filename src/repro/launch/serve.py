"""Serving launcher: load (or init) a model and serve a request trace
through the continuous-batching engine.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduce \
      --requests 8 --new-tokens 16
  # staggered mixed-length trace, static-batch baseline for comparison:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduce \
      --requests 16 --slots 4 --stagger 2 --policy static
"""
from __future__ import annotations

import argparse
import contextlib
import time

import numpy as np
import jax

from repro.common import param as pm
from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import get_config
from repro.core import router as router_lib
from repro.launch.mesh import make_host_mesh
from repro.launch.train import reduced
from repro.models import lm
from repro.serve.engine import ServeConfig, ServeEngine
from repro.sharding import context as ctx_lib
from repro.train.checkpoint import CheckpointManager


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir to restore params from")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--slots", type=int, default=None,
                    help="slot-pool size (default: min(requests, 8))")
    ap.add_argument("--stagger", type=int, default=0,
                    help="admit one request every N engine steps")
    ap.add_argument("--policy", choices=("continuous", "static"),
                    default="continuous",
                    help="static = batch-drain baseline")
    ap.add_argument("--kernel-backend", default=None,
                    choices=["ref", "pallas"],
                    help="MoE kernel backend override (docs/kernels.md); "
                         "default: the arch config's choice")
    ap.add_argument("--router-policy", default=None,
                    help="routing policy override (docs/routing.md)")
    ap.add_argument("--capacity-factor", type=float, default=None,
                    help="capacity-factor override (RouterSpec)")
    ap.add_argument("--moa-k", type=int, default=None,
                    help="MoA head-groups-per-token override (archs with "
                         "moa_positions; docs/moa.md)")
    ap.add_argument("--no-dead-slot-mask", action="store_true",
                    help="let dead slots route through the MoE (pre-"
                         "router behavior; more capacity overflow)")
    ap.add_argument("--no-prefill-buckets", action="store_true",
                    help="exact-length prefill (one jit per distinct "
                         "prompt length)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: ingest prompts longer than "
                         "this many tokens as a sequence of chunk "
                         "work-items interleaved with decode steps "
                         "(0 = whole-prompt prefill)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="max prompt tokens of prefill per engine step "
                         "(0 = unlimited)")
    ap.add_argument("--admission", choices=("fcfs", "aware"),
                    default="fcfs",
                    help="aware = prompt-length-aware: skip queued "
                         "requests whose next chunk does not fit the "
                         "step's remaining prefill budget")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="shared-prefix radix KV cache: retired pages "
                         "seed a prefix trie and later requests prefill "
                         "only their uncached tail (requires "
                         "--prefill-chunk; docs/serving.md)")
    ap.add_argument("--prefix-cache-bytes", type=int, default=1 << 30,
                    help="LRU byte budget for cached prefix pages "
                         "(<= 0 = unlimited)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give every request the same N-token prompt "
                         "prefix (exercises --prefix-cache)")
    ap.add_argument("--fused-decode", action="store_true",
                    help="one fused kernel launch per MoE/MoA layer at "
                         "decode (routing + dispatch + expert FFN + "
                         "combine; outputs match the unfused path within "
                         "float tolerance — docs/kernels.md §Fused "
                         "decode step)")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="write one profiler trace of the run under DIR: "
                         "device ops and the engine's serve.* spans on "
                         "one clock (.xplane.pb, and perfetto_trace.json.gz "
                         "for Perfetto; docs/observability.md)")
    ap.add_argument("--trace-sync", action="store_true",
                    help="calibration tracing: block on device results "
                         "inside prefill/decode spans so durations are "
                         "real op walls (costs ~2%% lost overlap)")
    ap.add_argument("--log-decisions", action="store_true",
                    help="record per-step scheduler StepDecision entries "
                         "(the replay simulator's fidelity contract)")
    args = ap.parse_args()
    print(f"[serve] compile cache: {enable_compile_cache()}")

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    if args.kernel_backend is not None:
        cfg = cfg.replace(kernel_backend=args.kernel_backend)
    if args.router_policy is not None or args.capacity_factor is not None:
        spec = router_lib.resolve_spec(cfg)
        if args.router_policy is not None:
            spec = spec.replace(policy=args.router_policy)
        if args.capacity_factor is not None:
            spec = spec.replace(capacity_factor=args.capacity_factor)
        router_lib.get_policy(spec.policy)
        cfg = cfg.replace(router=spec)
        print(f"[serve] router: {spec}")
    if args.moa_k is not None:
        if not cfg.moa_positions:
            raise SystemExit(
                f"--moa-k: arch {cfg.name!r} has no MoA layers "
                "(moa_positions is empty)")
        cfg = cfg.replace(moa_k=args.moa_k)
        print(f"[serve] moa_k: {cfg.moa_k}/{cfg.moa_experts} head groups")
    params = pm.materialize(lm.lm_defs(cfg), jax.random.PRNGKey(0))
    if args.ckpt:
        mgr = CheckpointManager(args.ckpt)
        step = mgr.latest_step()
        state_like = {"params": params}
        restored, _, _ = mgr.restore(step, state_like)
        params = restored["params"]
        print(f"[serve] restored checkpoint step {step}")

    if len(jax.devices()) > 1:
        ctx = ctx_lib.MeshContext.for_mesh(make_host_mesh(), "decode_std")
    else:
        ctx = ctx_lib.MeshContext.null(plan="decode_std")
    n_slots = args.slots or min(args.requests, 8)
    max_len = args.prompt_len + args.new_tokens + 1
    if args.prefill_chunk > 0:
        # chunk writes land in [start, start + chunk) windows: size the
        # page to a chunk multiple so the final padded window fits.
        max_len = -(-max_len // args.prefill_chunk) * args.prefill_chunk
    engine = ServeEngine(params, cfg, ServeConfig(
        max_len=max_len,
        temperature=args.temperature, n_slots=n_slots,
        policy=args.policy,
        mask_dead_slots=not args.no_dead_slot_mask,
        prefill_buckets=not args.no_prefill_buckets,
        prefill_chunk=args.prefill_chunk,
        prefill_budget=args.prefill_budget,
        admission=args.admission,
        prefix_cache=args.prefix_cache,
        prefix_cache_bytes=args.prefix_cache_bytes,
        fused_decode=args.fused_decode,
        trace_sync=args.trace_sync,
        log_decisions=args.log_decisions), ctx=ctx)
    rng = np.random.RandomState(0)
    shared = rng.randint(1, cfg.vocab_size,
                         (min(args.shared_prefix, args.prompt_len),))
    reqs = [engine.submit(
                np.concatenate([shared, rng.randint(
                    1, cfg.vocab_size,
                    (args.prompt_len - shared.shape[0],))]),
                args.new_tokens, arrival=i * args.stagger)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    with (jax.profiler.trace(args.trace, create_perfetto_trace=True)
          if args.trace else contextlib.nullcontext()):
        engine.run()
    dt = time.perf_counter() - t0
    total = engine.stats["generated_tokens"]
    print(f"[serve] {args.requests} requests x {args.new_tokens} tokens in "
          f"{dt:.2f}s ({total/dt:.1f} tok/s on {jax.default_backend()}, "
          f"policy={args.policy}, slots={n_slots}, "
          f"steps={engine.stats['decode_steps']}, "
          f"util={engine.slot_utilization:.2f})")
    print(f"[serve] prefill compiles: {len(engine.prefill_lengths)} "
          f"({sorted(engine.prefill_lengths)}; "
          f"buckets={'on' if engine._can_bucket else 'off'}, "
          f"dead-slot mask="
          f"{'on' if engine.sc.mask_dead_slots else 'off'})")
    if engine._chunk:
        print(f"[serve] chunked prefill: chunk={engine._chunk}, "
              f"budget={engine.sc.prefill_budget or 'unlimited'}, "
              f"admission={engine.sc.admission}, "
              f"chunks={engine.stats['prefill_chunks']} in "
              f"{engine.stats['prefill_calls']} calls, "
              f"offsets={sorted(engine.chunk_offsets)}")
    if engine.prefix is not None:
        ps = engine.prefix.stats
        print(f"[serve] prefix cache: {ps['hits']} hits / "
              f"{ps['hits'] + ps['misses']} lookups, "
              f"{ps['hit_tokens']} prompt tokens reused, "
              f"{engine.prefix.n_pages} pages "
              f"({engine.prefix.bytes / 1e6:.1f} MB, "
              f"{ps['evictions']} evictions)")
    if engine.telemetry:
        if any("expert_load" in t for t in engine.telemetry):
            load = np.sum([t["expert_load"] for t in engine.telemetry
                           if "expert_load" in t], axis=0)
            over = engine.stats["overflow_total"]
            print(f"[serve] expert load (decode): "
                  f"{load.astype(int).tolist()} "
                  f"(capacity overflow: {over:.0f})")
        if any("moa_load" in t for t in engine.telemetry):
            load = np.sum([t["moa_load"] for t in engine.telemetry
                           if "moa_load" in t], axis=0)
            over = engine.stats["moa_overflow_total"]
            print(f"[serve] MoA head-group load (decode): "
                  f"{load.astype(int).tolist()} "
                  f"(capacity overflow: {over:.0f})")
    if args.trace:
        print(f"[serve] profiler trace written under {args.trace} "
              "(perfetto_trace.json.gz loads in Perfetto)")
    if args.log_decisions:
        print(f"[serve] decision log: {len(engine.sched.decision_log)} "
              "scheduling steps recorded")
    print(f"[serve] sample: {reqs[0].tokens[:10]}")


if __name__ == "__main__":
    main()
