"""Continuous-batching serving engine.

The engine owns ``n_slots`` sequence slots and runs a step loop of

    schedule (admission + chunk planning under the prefill-token budget)
             -> run this step's prefill work-items -> fused decode step
             -> sample -> retire finished slots

Requests are admitted and retired *independently* (continuous batching):
the moment a sequence finishes — EOS or length budget, checked uniformly
for every sampled token including the last — its slot returns to the pool
and the next queued request prefills into it.  No batch-drain stalls: a
mixed-length batch never decodes into dead slots while stragglers finish
(the static-batch baseline that does is kept as ``policy="static"`` for
the serve benchmark).

Prompt ingestion is either whole-prompt (power-of-two buckets) or — with
``ServeConfig.prefill_chunk`` — *chunked*: long prompts become a sequence
of fixed-size chunk work-items spread over consecutive steps, each
resuming the slot's cache page where the previous chunk ended
(``lm_prefill(start_pos=...)``), so one monster prompt no longer stalls
every live decode slot for a whole prefill.  ``prefill_budget`` bounds
the prompt tokens any step may ingest and ``admission="aware"`` lets
short prompts pass a long head-of-line prompt within the leftover budget
(scheduler.py has the planning; docs/serving.md the design).

Device-side structure per step: at most ``prefill_budget`` tokens of
batch-1 prefill work (one jit per bucket, one per chunk offset) plus
exactly one fused decode call over the fully-ingested slots with
*per-slot* positions (``lm_decode`` takes a [n_slots] position vector —
slots of mixed age each attend at their own offset; mid-prefill slots
are masked out like dead ones).

Plans: prefill runs under ``prefill_tp`` (dispatch capacity sharded over
data), decode under ``decode_std`` (weights stay sharded, KV sequence over
model).  The handoff is an explicit ``MeshContext.reshard`` — device_put
of the prefilled page onto the decode plan — before the page is inserted
into the slot pool (ROADMAP: the prefill→decode boundary now reshards).

Observability (docs/observability.md): the engine's bookkeeping lives in
a typed ``MetricsRegistry`` (``engine.metrics``; the legacy ``.stats``
dict is a property view over it), per-step MoE expert load / overflow
aggregates into bounded histogram/counter instruments plus a
``keep_last_n`` ring of raw entries (``engine.telemetry``).  Every step
is a ``serve.step`` span whose named children cover its host path
(schedule and admission, input building, prefill calls, decode, the
device->host sync of sampling, telemetry, token append and retirement,
KV insert, prefix probe/hit).  Spans are profiler annotations: under
``jax.profiler.trace`` they share a clock with the device ops, so device
idle time can be put down to the host work that ran in it.  The jitted
programs carry names (``jit_prefill``, ``jit_prefill_chunk``,
``jit_decode_step``, ``jit_sample_argmax``) that the device trace shows.
With ``ServeConfig.trace_path`` set the spans are also kept as a chrome
trace, which feeds the cost-model replay simulator
(``repro.obs.replay``).

Batching-invariance caveat: all pool slots (active *and* dead) share the
MoE capacity buffers of one fused decode, so greedy outputs are
bit-identical to sequential generation only while no decode-time
capacity overflow occurs (ample ``capacity_factor`` relative to
``n_slots``).  Under routing skew past capacity, which sequences share a
step determines what drops — exactly the events the per-step
``overflow`` telemetry counts, so the regime is observable.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import param as pm
from repro.configs.base import ModelConfig
from repro.models import lm
from repro.obs import metrics as metrics_lib
from repro.obs import trace as trace_lib
from repro.serve.kv_cache import PrefixCache, SlotKVCache
from repro.serve.scheduler import (Request, RequestQueue, Scheduler,
                                   chunk_rounds)
from repro.sharding import context as ctx_lib


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 256           # slot page length (prompt + new tokens)
    temperature: float = 0.0     # 0 => greedy
    eos_id: int = -1             # -1 => never stop early
    seed: int = 0
    n_slots: int = 8             # slot-pool size == decode batch width
    policy: str = "continuous"   # "continuous" | "static" (drain baseline)
    prefill_plan: str = "prefill_tp"
    decode_plan: str = "decode_std"
    # Dead-slot masking: pass slot occupancy into routing (the router's
    # token-validity mask) so empty pool slots neither route through the
    # MoE nor consume expert capacity — observable as lower capacity-
    # overflow telemetry under partial occupancy.
    mask_dead_slots: bool = True
    # Bucketed prefill: right-pad prompts to power-of-two length buckets
    # so jit compiles once per bucket instead of once per distinct prompt
    # length.  Padded positions are masked out of MoE routing and their
    # garbage KV is never attended (causal mask + sequential overwrite),
    # so outputs stay bit-identical to exact-length prefill while prefill
    # routing does not overflow at the exact length (capacity is sized
    # from the padded count, so padding only ever ADDS slots; under a
    # factor tight enough to drop prompt tokens the two runs keep
    # different assignments — docs/serving.md).  Disabled automatically
    # for ssm/hybrid (stateful scan) and sliding-window models
    # (ring-buffer caches would retain padded positions).
    prefill_buckets: bool = True
    min_bucket: int = 8          # smallest prefill bucket length
    # Chunked prefill (docs/serving.md): prompts longer than
    # ``prefill_chunk`` tokens are ingested as a sequence of fixed-size
    # chunk work-items spread over consecutive engine steps, each resuming
    # the cache where the previous chunk ended (lm_prefill start_pos) —
    # decode keeps running between chunks, so one long prompt no longer
    # stalls every live decode slot for a whole monster prefill.  0
    # disables (whole-prompt prefill, the pre-chunking behavior).  Same
    # architecture restrictions as bucketing (ssm/hybrid, sliding-window):
    # the engine falls back loudly (RuntimeWarning) when unsupported.
    prefill_chunk: int = 0
    # Max prompt tokens of prefill work any single engine step may carry
    # (0 = unlimited).  Enforced by the Scheduler; with chunking enabled
    # the chunk size must fit the budget.  The budget counts *real*
    # prompt tokens: device work is chunk-/bucket-granular (a final
    # partial chunk pads to the chunk size, a whole prompt to its
    # power-of-two bucket), so the per-step device-token bound is the
    # budget rounded up to those granularities — use chunking for tight
    # stall bounds (buckets can pad up to 2x).
    prefill_budget: int = 0
    # Admission policy: "fcfs" pops strictly in arrival order; "aware"
    # (prompt-length-aware) skips requests whose next chunk does not fit
    # the step's remaining prefill budget and admits the earliest one
    # that does, so short prompts never queue behind a long head-of-line
    # prompt.
    admission: str = "fcfs"
    # Shared-prefix radix KV cache (docs/serving.md §Shared-prefix KV
    # cache): retired slot pages are inserted into a prefix trie keyed by
    # prefill_chunk-token prompt blocks; a new request resumes from the
    # longest cached block-aligned prefix and prefills only the tail.
    # Requires chunked prefill (prefill_chunk > 0) — hits land on the
    # chunk grid, so a resumed prefill replays the exact jitted chunk
    # calls a cold one would and greedy outputs stay bit-identical with
    # the cache on or off.  Architectures that refuse chunking
    # (ssm/hybrid, sliding-window) also disable the prefix cache (with
    # the same RuntimeWarning fallback).
    prefix_cache: bool = False
    # LRU byte budget for cached prefix pages (<= 0 = unlimited).
    # Accounting charges the full per-page byte size for every entry;
    # pinned entries (in-flight prefills) are never evicted.
    prefix_cache_bytes: int = 1 << 30
    # Chrome-trace span capture (docs/observability.md): when set, the
    # engine's spans (schedule, prefix probe/hit, chunk-group prefill
    # with [G, C] attrs, reshard, decode, sample, retire, ...) are also
    # kept in memory on the host clock and ``run()`` writes them here as
    # a Perfetto-loadable trace, the input of the cost-model fit.  None
    # (the default): spans are profiler annotations alone, recorded only
    # under ``jax.profiler.trace``; outputs are bit-identical either way.
    trace_path: str | None = None
    # Calibration tracing: block on device results *inside* the prefill/
    # decode spans so each span's duration is that op's real wall, in a
    # profiler trace and in the chrome trace alike (the replay cost model
    # fits on the latter — ``make fit-costs`` sets this).
    # Off (the default), spans record dispatch time and device time
    # drains at the step's natural sync points: the trace stays accurate
    # at step granularity and the capture overhead is the span appends
    # alone (<1% on the serve bench; the syncs cost another ~2% in lost
    # host/device overlap — docs/observability.md §Overhead discipline).
    trace_sync: bool = False
    # Capture scheduler decisions (admission order, chunk plan, prefix
    # hits) as StepDecision records on ``engine.sched.decision_log`` —
    # the fidelity contract the replay simulator reproduces.
    log_decisions: bool = False
    # Raw per-step MoE telemetry entries kept for inspection (a bounded
    # ring — the aggregate histogram/counter instruments in
    # ``engine.metrics`` cover the full run, so a week-long serve no
    # longer grows an unbounded list).
    telemetry_keep_last_n: int = 512
    # Fused single-launch MoE decode (docs/kernels.md §Fused decode
    # step): each MoE/MoA layer's decode hot path runs routing + scatter
    # + expert FFN + combine as ONE kernel launch.  Outputs match the
    # unfused path within float tolerance (tests/test_fused_decode.py);
    # the backend falls back per call (counted, RuntimeWarning) when the
    # fused slab exceeds the VMEM budget.  Decode-only — prefill stays
    # unfused.
    fused_decode: bool = False


class ServeEngine:
    def __init__(self, params, cfg: ModelConfig, sc: ServeConfig,
                 ctx: ctx_lib.MeshContext | None = None):
        if sc.fused_decode:
            # Flows to decode-shaped MoE/MoA calls only (the model layer
            # gates on decode=True); the jitted closures below capture
            # this local cfg, so flip it before they are built.
            cfg = cfg.replace(fused_decode=True)
        self.params = params
        self.cfg = cfg
        self.sc = sc
        # No trace_path => the null tracer: every span below is a profiler
        # annotation under a profiler session, else one shared no-op.
        self.tracer = (trace_lib.Tracer(sc.trace_path, process_name="serve")
                       if sc.trace_path else trace_lib.NULL)
        self._trace_sync = sc.trace_sync
        self.ctx = ctx or ctx_lib.MeshContext.null(plan=sc.decode_plan)
        on_mesh = self.ctx.mesh is not None
        self.decode_ctx = (self.ctx.with_plan(sc.decode_plan) if on_mesh
                           else self.ctx)
        self.prefill_ctx = (self.ctx.with_plan(sc.prefill_plan) if on_mesh
                            else self.ctx)
        # Bucketed prefill is only sound when a padded tail can neither
        # leak into recurrent state (ssm/hybrid mixers scan sequentially)
        # nor linger in a ring-buffer KV cache (sliding-window layers).
        from repro.configs.base import layer_kinds
        stateless = (not cfg.sliding_window
                     and all(k.mixer != "mamba" for k in layer_kinds(cfg)))
        self._can_bucket = sc.prefill_buckets and stateless
        # Chunked prefill shares the restriction (resuming mid-prompt
        # needs the whole prefix recoverable from the KV cache): refuse
        # loudly and fall back to whole-prompt prefill otherwise.
        self._chunk = 0
        if sc.prefill_chunk > 0:
            if not stateless:
                import warnings
                warnings.warn(
                    "chunked prefill requires stateless attention caches; "
                    "ssm/hybrid state scans and sliding-window ring "
                    "buffers cannot resume mid-prompt — falling back to "
                    "whole-prompt prefill (docs/serving.md)",
                    RuntimeWarning, stacklevel=2)
            else:
                c = sc.prefill_chunk
                if c % cfg.kv_block != 0 or (c > cfg.q_block
                                             and c % cfg.q_block != 0):
                    raise ValueError(
                        f"prefill_chunk={c} must be a multiple of "
                        f"kv_block={cfg.kv_block} (and of q_block="
                        f"{cfg.q_block} when larger) so chunk boundaries "
                        "stay block-aligned with whole-prompt prefill")
                if c > sc.max_len:
                    raise ValueError(
                        f"prefill_chunk={c} > max_len={sc.max_len}: even "
                        "a single chunk's cache write would not fit the "
                        "slot page")
                if jnp.dtype(cfg.param_dtype) != jnp.dtype(
                        cfg.compute_dtype):
                    # The cached prefix K/V a chunk attends round-trips
                    # through the cache dtype; a whole-prompt prefill
                    # attends fresh compute-dtype K/V, so a narrower
                    # cache breaks the bit-identical-to-whole-prompt
                    # guarantee (outputs stay valid, streams may differ).
                    import warnings
                    warnings.warn(
                        "chunked prefill with cache dtype "
                        f"{jnp.dtype(cfg.param_dtype).name} != compute "
                        f"dtype {jnp.dtype(cfg.compute_dtype).name}: "
                        "chunk attention reads the cached prefix at "
                        "cache precision, so outputs are not guaranteed "
                        "bit-identical to whole-prompt prefill "
                        "(docs/serving.md)", RuntimeWarning, stacklevel=2)
                self._chunk = c
        # Shared-prefix cache: hits must land on the chunk grid (a resumed
        # prefill replays the same jitted chunk calls a cold one would, so
        # greedy outputs stay bit-identical) — hence it requires chunked
        # prefill, and inherits the architecture fallback above.
        self._prefix_on = False
        if sc.prefix_cache:
            if sc.prefill_chunk <= 0:
                raise ValueError(
                    "prefix_cache requires chunked prefill "
                    "(prefill_chunk > 0): cache hits resume mid-prompt "
                    "on the chunk grid — whole-prompt prefill has no "
                    "resume path (docs/serving.md)")
            if self._chunk == 0:
                import warnings
                warnings.warn(
                    "prefix cache disabled: this architecture refused "
                    "chunked prefill (ssm/sliding-window), and prefix "
                    "hits can only resume through the chunk path "
                    "(docs/serving.md)", RuntimeWarning, stacklevel=2)
            else:
                self._prefix_on = True
        # jit names each program after its function: the device trace
        # shows jit_prefill(...), jit_decode_step(...) and the others.
        def prefill(p, b, c, li, v):
            return lm.lm_prefill(p, b, c, cfg, ctx=self.prefill_ctx,
                                 last_index=li, valid=v)

        def decode_step(p, t, c, i, v):
            return lm.lm_decode(p, t, c, i, cfg, ctx=self.decode_ctx,
                                valid=v, return_telemetry=True)

        def sample_argmax(logits):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def sample_categorical(keys, logits):
            return jax.vmap(lambda key, l: jax.random.categorical(
                key, l / sc.temperature).astype(jnp.int32))(keys, logits)

        self._prefill = jax.jit(prefill)
        # One jitted chunk function per chunk *offset* (chunk length is
        # fixed, so compile count is O(max_len / prefill_chunk)); the
        # static offset keeps the blockwise kv ranges pruned above the
        # shifted diagonal.
        self._chunk_fns: dict[int, object] = {}
        self._decode = jax.jit(decode_step)
        self._argmax = jax.jit(sample_argmax)
        if sc.temperature > 0.0:
            self._categorical = jax.jit(sample_categorical)
        self.reset()

    # -- lifecycle --------------------------------------------------------
    def reset(self) -> None:
        """Fresh queue/pool/stats/request ids (so a replayed trace samples
        the same per-request streams); compiled step functions are
        retained."""
        self._rid = 0
        self.kv = SlotKVCache(self.cfg, self.sc.n_slots, self.sc.max_len,
                              ctx=self.decode_ctx)
        # One immutable blank page, reused by every prefill (jax arrays
        # are never mutated in place, so sharing is safe).
        self._blank_page = pm.materialize(self.kv.seq_defs,
                                          jax.random.PRNGKey(0))
        # Shared-prefix radix cache over retired pages.  Page byte size is
        # the dense per-sequence page (every leaf of seq_defs) — uniform,
        # so LRU accounting is a multiple of one constant.
        self.prefix: PrefixCache | None = None
        self._pins: dict[int, object] = {}   # rid -> pinned trie entry
        # rid -> host clock at submit(), popped when the request admits
        self._submitted_at: dict[int, float] = {}
        if self._prefix_on:
            page_bytes = sum(
                int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
                for leaf in jax.tree_util.tree_leaves(self._blank_page))
            self.prefix = PrefixCache(
                block=self._chunk, page_bytes=page_bytes,
                max_bytes=self.sc.prefix_cache_bytes)
        self.queue = RequestQueue()
        self.sched = Scheduler(
            self.sc.n_slots, policy=self.sc.policy,
            admission=self.sc.admission,
            prefill_chunk=self._chunk,
            prefill_budget=self.sc.prefill_budget,
            prefix_probe=self._prefix_probe if self._prefix_on else None,
            on_admit=self._on_admit)
        if self.sc.log_decisions:
            self.sched.decision_log = []
        self.step_count = 0
        self.prefill_lengths: set[int] = set()   # distinct compiled shapes
        self.chunk_offsets: set[int] = set()     # distinct chunk compiles
        # Raw per-step MoE telemetry: a bounded ring (the full-run view
        # lives in the aggregate instruments below).
        self._telemetry = collections.deque(
            maxlen=max(self.sc.telemetry_keep_last_n, 0) or None)
        # Typed metrics registry (docs/observability.md).  The counter
        # names are the legacy engine.stats keys — the ``stats`` property
        # renders them as the same plain dict existing tests/benches read.
        # prefill_calls counts device prefill calls: < prefill_chunks when
        # cross-slot chunk batching groups same-offset work-items into one
        # multi-row call.
        self.metrics = metrics_lib.MetricsRegistry()
        self._c = {name: self.metrics.counter(name) for name in (
            "prefills", "decode_steps", "reshards", "generated_tokens",
            "slot_steps_active", "slot_steps_total", "overflow_total",
            "prefill_chunks", "prefill_tokens", "prefill_calls",
            "prefix_hits", "prefix_hit_tokens")}
        self._h_overflow = self.metrics.histogram("decode_overflow_per_step")
        self._h_active = self.metrics.histogram("decode_active_slots")
        self._c_expert_load = self.metrics.counter("decode_expert_load",
                                                   labels=("expert",))
        # MoA (routed attention head groups, docs/moa.md): separate
        # instrument families — head-group load is not FFN-expert load.
        self._c_moa_overflow = self.metrics.counter("moa_overflow_total")
        self._h_moa_overflow = self.metrics.histogram(
            "decode_moa_overflow_per_step")
        self._c_moa_load = self.metrics.counter("decode_moa_load",
                                                labels=("expert",))

    def submit(self, prompt, max_new_tokens: int, arrival: int = 0
               ) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if max_new_tokens < 1:
            # The engine samples a first token unconditionally when a
            # prefill completes, so a zero budget would still return one
            # token (off-by-one); reject at the front door instead.
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}: "
                "prefill always samples the first token")
        if prompt.shape[0] + max_new_tokens > self.sc.max_len:
            raise ValueError(
                f"prompt ({prompt.shape[0]}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_len {self.sc.max_len}")
        if (self._chunk == 0 and self.sc.prefill_budget > 0
                and prompt.shape[0] > self.sc.prefill_budget):
            why = ("this architecture refused chunked prefill "
                   "(ssm/sliding-window — see the construction warning), "
                   "so the whole prompt must fit the budget"
                   if self.sc.prefill_chunk > 0 else
                   "chunked prefill is off — enable "
                   "ServeConfig.prefill_chunk to split it")
            raise ValueError(
                f"prompt ({prompt.shape[0]}) exceeds the per-step prefill "
                f"budget ({self.sc.prefill_budget}) and {why}")
        if self._chunk and prompt.shape[0] > self._chunk:
            # Every chunk ships a full prefill_chunk-token buffer (the
            # final one padded), so its cache write spans
            # [start, start + chunk); a window past max_len would make
            # the dynamic_update_slice clamp its start and silently
            # overwrite already-cached prefix positions.
            padded = -(-int(prompt.shape[0]) // self._chunk) * self._chunk
            if padded > self.sc.max_len:
                raise ValueError(
                    f"prompt ({prompt.shape[0]}) rounds up to {padded} "
                    f"chunk-padded tokens > max_len {self.sc.max_len}: "
                    "the final chunk's cache write would not fit the "
                    "page — raise max_len or lower prefill_chunk")
        req = Request(rid=self._rid, prompt=prompt,
                      max_new_tokens=max_new_tokens, arrival=arrival)
        self._submitted_at[req.rid] = time.perf_counter()
        self._rid += 1
        self.queue.push(req)
        return req

    # -- sampling ---------------------------------------------------------
    def _req_key(self, req: Request):
        """Per-request stream: deterministic regardless of which batch the
        request happens to share a decode step with."""
        return jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self.sc.seed), req.rid),
            len(req.tokens))

    def _sample_rows(self, logits, reqs: list[Request | None]) -> np.ndarray:
        """logits: [B, V] -> [B] int32 (row i sampled for reqs[i]).  The
        host waits for the device in ``serve.sync``."""
        if self.sc.temperature <= 0.0:
            ids = self._argmax(logits)
        else:
            keys = jnp.stack([
                self._req_key(r) if r is not None
                else jax.random.PRNGKey(0) for r in reqs])
            ids = self._categorical(keys, logits)
        with self.tracer.span("serve.sync", rows=len(reqs)):
            return np.asarray(ids)

    # -- the step loop ----------------------------------------------------
    def _append_token(self, req: Request, tok: int, slot: int) -> None:
        """Record a sampled token and retire uniformly on EOS/length.

        EOS is checked for *every* sampled token — including the final one
        of the budget (the old static engine skipped the check when
        ``i == max_new_tokens - 1``, so a terminal EOS was reported as a
        length stop)."""
        req.tokens.append(int(tok))
        self._c["generated_tokens"].inc()
        if self.sc.eos_id >= 0 and int(tok) == self.sc.eos_id:
            req.done_reason = "eos"
        elif len(req.tokens) >= req.max_new_tokens:
            req.done_reason = "length"
        if req.done:
            req.finished_step = self.step_count
            with self.tracer.span("serve.retire", rid=req.rid, slot=slot,
                                  reason=req.done_reason):
                self.sched.retire(slot)
                if self.prefix is not None and not self.prefix.covered(
                        req.prompt):
                    # Retirement feeds the trie: the slot page's prompt
                    # span [0, prompt_len) is canonical chunk-prefill
                    # output (KV the decode steps wrote lives at positions
                    # >= prompt_len — inside the page but outside any
                    # possible hit, so it rides along inert).  covered()
                    # keeps the hot path free of extracts when the prefix
                    # is already cached.
                    self.prefix.insert(req.prompt, self.kv.extract(slot))
                self.kv.release(slot)

    def _bucket_len(self, plen: int) -> int:
        """Power-of-two length bucket for a prompt (clamped to the page)."""
        if not self._can_bucket:
            return plen
        b = max(self.sc.min_bucket, 1)
        while b < plen:
            b *= 2
        return min(b, self.sc.max_len)

    def _start(self, slot: int, req: Request) -> None:
        """Prefill a newly admitted request and seed its slot.

        Prompts are right-padded to a power-of-two bucket (one jit compile
        per *bucket* instead of per distinct prompt length); the padded
        tail is masked out of MoE routing (router token-validity mask) and
        its KV is causally invisible at the logits position and
        overwritten slot-by-slot as decode proceeds, so bucketing is
        bit-identical to exact-length prefill as long as prefill routing
        does not overflow at the exact length (padding only adds
        capacity; see docs/serving.md)."""
        plen = req.prompt_len
        blen = self._bucket_len(plen)
        tr = self.tracer
        with tr.span("serve.inputs", rows=1, tokens=blen):
            padded = np.zeros((blen,), np.int32)
            padded[:plen] = req.prompt
            valid = np.zeros((1, blen), np.float32)
            valid[0, :plen] = 1.0
            tokens = jnp.asarray(padded, jnp.int32)[None, :]
            last = jnp.asarray(plen - 1, jnp.int32)
            valid = jnp.asarray(valid)
        self.prefill_lengths.add(blen)
        with tr.span("serve.prefill", rid=req.rid, slot=slot, plen=plen,
                     tokens=blen):
            logits, page = self._prefill(self.params, {"tokens": tokens},
                                         self._blank_page, last, valid)
            if self._trace_sync:
                logits = jax.block_until_ready(logits)
        if self.ctx.mesh is not None:
            # prefill_tp -> decode_std boundary: explicit reshard of the
            # page onto the decode plan before it joins the slot pool.
            with tr.span("serve.reshard", rid=req.rid, slot=slot):
                page = self.decode_ctx.reshard(page, self.kv.seq_defs)
            self._c["reshards"].inc()
        with tr.span("serve.kv_insert", slot=slot):
            self.kv.insert(slot, page, req.prompt_len)
        self._c["prefills"].inc()
        self._c["prefill_calls"].inc()
        self._c["prefill_tokens"].inc(plen)
        req.prefill_pos = plen
        req.first_token_step = self.step_count
        with tr.span("serve.sample", rows=1):
            tok = self._sample_rows(logits, [req])[0]
        self._append_token(req, tok, slot)

    # -- shared-prefix cache hooks ----------------------------------------
    def _prefix_probe(self, req: Request) -> int:
        """Scheduler hook: cached-prefix length a new request would resume
        from (admission charges only the uncached tail)."""
        with self.tracer.span("serve.prefix_probe", rid=req.rid):
            return self.prefix.probe(req.prompt)

    def _on_admit(self, slot: int, req: Request) -> None:
        """Scheduler hook, fired the moment a request claims a slot: a
        ``serve.admit`` span with the time the request waited since
        ``submit()``, and the prefix-cache lookup when the cache is on."""
        t = self._submitted_at.pop(req.rid, None)
        wait = ({} if t is None
                else {"wait_ms": (time.perf_counter() - t) * 1e3})
        with self.tracer.span("serve.admit", rid=req.rid, slot=slot, **wait):
            if self._prefix_on:
                self._prefix_admit(slot, req)

    def _prefix_admit(self, slot: int, req: Request) -> None:
        """Alias the longest cached block-aligned prefix page into the
        slot (staged, exactly like a partial chunked-prefill page) and
        advance ``prefill_pos`` so chunk planning covers only the tail.
        The trie entry stays pinned until the prefill completes."""
        hit, page, entry = self.prefix.lookup(req.prompt)
        if hit <= 0:
            return
        with self.tracer.span("serve.prefix_hit", rid=req.rid, slot=slot,
                              hit_tokens=hit):
            self._pins[req.rid] = entry
            req.prefill_pos = hit
            # Zero-copy alias: jax pages are immutable, so staging the
            # cached page is safe — the tail chunk's cache update
            # materializes the "copy" as fresh arrays.
            self.kv.append(slot, page, hit, last=False)
        self._c["prefix_hits"].inc()
        self._c["prefix_hit_tokens"].inc(hit)

    # -- chunked prefill ---------------------------------------------------
    def _chunk_fn(self, off: int):
        """Jitted prefill for one chunk offset (static start_pos).  One
        function object per offset; jit itself specializes per [G, C]
        batch shape, so grouped calls of different widths coexist."""
        fn = self._chunk_fns.get(off)
        if fn is None:
            def prefill_chunk(p, b, c, li, v):
                return lm.lm_prefill(p, b, c, self.cfg, ctx=self.prefill_ctx,
                                     last_index=li, valid=v, start_pos=off)
            fn = jax.jit(prefill_chunk)
            self._chunk_fns[off] = fn
        return fn

    def _resume_page(self, slot: int):
        """Base page a slot's next chunk resumes from: the staged
        in-flight page, else blank.  Explicit ``is None`` — ``staged(...)
        or blank`` would ask the page pytree for truthiness, which
        raises on bare jax-array leaves and silently restarts the
        prefill for empty-container ones."""
        page = self.kv.staged(slot)
        return self._blank_page if page is None else page

    def _run_chunk_rounds(self, by_slot: dict) -> None:
        """Ingest this step's chunk work-items, batching across slots.

        The round/grouping plan comes from ``scheduler.chunk_rounds`` —
        the same function the replay simulator charges costs against, so
        the simulated call pattern is the real one by construction.
        Under a per-step budget most slots carry exactly one chunk, so a
        round typically batches the whole step's chunk work into one or
        two device calls (``_run_chunk_group``)."""
        for off, group in chunk_rounds(by_slot):
            self._run_chunk_group(off, group)

    def _run_chunk_group(self, off: int, group: list) -> None:
        """One multi-row prefill call for same-offset chunk work-items of
        ``len(group)`` different slots.  Rows are padded to a power-of-two
        batch (pad rows: blank page, all-zero validity — masked out of
        routing exactly like dead decode slots).  In-flight pages stay
        *staged* in the SlotKVCache between steps and fold into the pool
        only on the completing chunk — a mid-prefill slot never decodes,
        so per-chunk pool blends (and on-mesh reshards) would be pure
        hot-path overhead.  Completing rows sample their first token."""
        c = self._chunk
        g = len(group)
        gp = 1 << (g - 1).bit_length()          # power-of-two batch bucket
        tr = self.tracer
        with tr.span("serve.inputs", rows=gp, tokens=gp * c):
            tokens = np.zeros((gp, c), np.int32)
            valid = np.zeros((gp, c), np.float32)
            li = np.full((gp,), c - 1, np.int32)  # pad rows: clamped, unread
            pages = []
            for i, (slot, w) in enumerate(group):
                req = w.req
                tokens[i, :w.length] = req.prompt[w.start:w.start + w.length]
                valid[i, :w.length] = 1.0
                # Chunk-local index of the final prompt token (only read
                # on a row's last chunk; clamped elsewhere).
                li[i] = min(req.prompt_len - 1 - off, c - 1)
                pages.append(self._resume_page(slot))
            pages.extend([self._blank_page] * (gp - g))
            page_in = pages[0] if gp == 1 else self.kv.stack_pages(pages)
            tokens, li, valid = (jnp.asarray(tokens), jnp.asarray(li),
                                 jnp.asarray(valid))
        self.chunk_offsets.add(off)
        with tr.span("serve.prefill_chunk", offset=off, G=g, Gp=gp, C=c,
                     tokens=gp * c):
            logits, page_out = self._chunk_fn(off)(
                self.params, {"tokens": tokens}, page_in, li, valid)
            if self._trace_sync:
                logits = jax.block_until_ready(logits)
            out_pages = ([page_out] if gp == 1
                         else self.kv.split_pages(page_out, g))
        self._c["prefill_calls"].inc()
        self._c["prefill_chunks"].inc(g)
        rows: list[Request | None] = [None] * gp
        done_rows = []
        for i, (slot, w) in enumerate(group):
            req = w.req
            req.prefill_pos = w.start + w.length
            self._c["prefill_tokens"].inc(w.length)
            page = out_pages[i]
            done = not req.prefilling
            if done and self.ctx.mesh is not None:
                # staged pages stayed on the prefill plan; each finished
                # page reshards once, exactly like a whole-prompt page.
                with tr.span("serve.reshard", rid=req.rid, slot=slot):
                    page = self.decode_ctx.reshard(page, self.kv.seq_defs)
                self._c["reshards"].inc()
            with tr.span("serve.kv_insert", slot=slot):
                self.kv.append(slot, page, req.prefill_pos, last=done)
            if done:
                self._c["prefills"].inc()
                req.first_token_step = self.step_count
                if self.prefix is not None:
                    entry = self._pins.pop(req.rid, None)
                    if entry is not None:
                        # the tail chunks no longer read the cached base
                        # page — the entry is evictable again.
                        self.prefix.unpin(entry)
                rows[i] = req
                done_rows.append((i, slot, req))
        if done_rows:
            with tr.span("serve.sample", rows=len(done_rows)):
                toks = self._sample_rows(logits, rows)
            for i, slot, req in done_rows:
                self._append_token(req, toks[i], slot)

    def step(self) -> int:
        """One engine step: plan prefill work (admission + chunks under
        the per-step token budget), run it, then one fused decode over
        the fully-prefilled slots, sample, retire.  Returns the number of
        slots that were active in the decode."""
        tr = self.tracer
        with trace_lib.use(tr), tr.span("serve.step", step=self.step_count):
            return self._step_body(tr)

    def _step_body(self, tr) -> int:
        by_slot: dict[int, list] = {}
        with tr.span("serve.schedule", queued=len(self.queue)):
            work = self.sched.schedule_prefill(self.queue, self.step_count)
        for w in work:
            if (not self._prefix_on and w.start == 0
                    and w.length == w.req.prompt_len):
                self._start(w.slot, w.req)   # whole prompt: bucketed path
            else:
                # With the prefix cache on, even single-chunk prompts take
                # the chunk path: every cached page must be built from the
                # canonical same-offset chunk calls, or a later resumed
                # prefill would mix pages from differently-shaped jits and
                # forfeit bit-identity with the cache off.
                by_slot.setdefault(w.slot, []).append(w)
        self._run_chunk_rounds(by_slot)
        active = self.sched.decoding()
        if active:
            n = self.sc.n_slots
            with tr.span("serve.inputs", rows=n):
                toks = np.zeros((n,), np.int32)
                pos = np.zeros((n,), np.int32)
                occ = np.zeros((n,), np.float32)
                rows: list[Request | None] = [None] * n
                for slot, req in active:
                    toks[slot] = req.tokens[-1]
                    # position of the token being fed (the one just
                    # sampled).
                    pos[slot] = req.prompt_len + len(req.tokens) - 1
                    occ[slot] = 1.0
                    rows[slot] = req
                # Slot-occupancy mask: dead slots are masked out of MoE
                # routing so they stop consuming expert capacity.
                if not self.sc.mask_dead_slots:
                    occ[:] = 1.0
                args = (jnp.asarray(toks), jnp.asarray(pos),
                        jnp.asarray(occ))
            with tr.span("serve.decode", active=len(active), slots=n):
                logits, self.kv.cache, telem = self._decode(
                    self.params, args[0], self.kv.cache, args[1], args[2])
                if self._trace_sync:
                    logits = jax.block_until_ready(logits)
            with tr.span("serve.sample", rows=len(active)):
                nxt = self._sample_rows(logits, rows)
            self._record_telemetry(telem, len(active))
            with tr.span("serve.append", rows=len(active)):
                self._c["decode_steps"].inc()
                self._c["slot_steps_active"].inc(len(active))
                self._c["slot_steps_total"].inc(n)
                for slot, req in active:
                    # the fed token's KV was just written at pos[slot]
                    self.kv.lengths[slot] = int(pos[slot]) + 1
                    self._append_token(req, nxt[slot], slot)
        if tr.enabled:
            tr.counter("serve.queue", depth=len(self.queue))
            tr.counter("serve.slots", active=len(active))
        self.step_count += 1
        return len(active)

    def run(self, max_steps: int | None = None) -> None:
        """Drive the step loop until every submitted request completes;
        with tracing on, the trace file is (re)written at the end."""
        steps = 0
        while self.queue or self.sched.active():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        if self.tracer.enabled and self.tracer.path:
            self.tracer.save()

    # -- telemetry --------------------------------------------------------
    def _record_telemetry(self, telem, n_active: int) -> None:
        if telem is None:
            return
        with self.tracer.span("serve.telemetry"):
            entry = {"step": self.step_count, "active": n_active}
            # Aggregate instruments cover the whole run in bounded memory;
            # the raw entry lands in the keep_last_n ring for inspection.
            # MoE FFN counters and MoA head-group counters are independent
            # families — a model may have either or both.
            if "expert_load" in telem:
                entry.update(expert_load=np.asarray(telem["expert_load"]),
                             overflow=np.asarray(telem["overflow"]),
                             n_moe=float(telem["n_moe"]))
                self._c["overflow_total"].inc(float(entry["overflow"].sum()))
                self._h_overflow.observe(float(entry["overflow"].sum()))
                for e, load in enumerate(entry["expert_load"].tolist()):
                    self._c_expert_load.child(expert=e).inc(float(load))
            if "moa_load" in telem:
                entry.update(moa_load=np.asarray(telem["moa_load"]),
                             moa_overflow=np.asarray(telem["moa_overflow"]),
                             n_moa=float(telem["n_moa"]))
                self._c_moa_overflow.inc(float(entry["moa_overflow"].sum()))
                self._h_moa_overflow.observe(
                    float(entry["moa_overflow"].sum()))
                for e, load in enumerate(entry["moa_load"].tolist()):
                    self._c_moa_load.child(expert=e).inc(float(load))
            self._h_active.observe(n_active)
            self._telemetry.append(entry)

    @property
    def telemetry(self) -> list:
        """Recent raw per-step MoE telemetry entries (bounded ring of the
        last ``telemetry_keep_last_n`` decode steps, as a list)."""
        return list(self._telemetry)

    @property
    def stats(self) -> dict:
        """Legacy flat stats view over the typed metrics registry."""
        return self.metrics.stats()

    @property
    def slot_utilization(self) -> float:
        total = self._c["slot_steps_total"].value
        return self._c["slot_steps_active"].value / total if total else 0.0

    # -- static-batch-compatible front door -------------------------------
    def generate(self, prompts: np.ndarray, max_new_tokens: int
                 ) -> np.ndarray:
        """prompts: [B, S0] int32 (same length). Returns [B, new] tokens.

        Convenience wrapper over submit/run on a freshly reset engine: all
        B requests arrive at step 0 and rows finishing early (EOS) are
        padded with ``eos_id``."""
        prompts = np.asarray(prompts)
        if prompts.shape[0] > self.sc.n_slots:
            raise ValueError(
                f"{prompts.shape[0]} prompts > n_slots={self.sc.n_slots}; "
                f"submit() + run() handles oversubscription")
        self.reset()
        reqs = [self.submit(p, max_new_tokens) for p in prompts]
        self.run()
        width = max(len(r.tokens) for r in reqs)
        pad = self.sc.eos_id if self.sc.eos_id >= 0 else 0
        out = np.full((len(reqs), width), pad, np.int32)
        for i, r in enumerate(reqs):
            out[i, :len(r.tokens)] = r.tokens
        return out
