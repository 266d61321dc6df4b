"""Serve cells: the program's ``ServeEngine`` driven by the cell's traffic.

Set-up makes the weights on the device, builds the engine, and compiles
every program the traffic can reach: the whole-prompt prefill of each
length bucket, the chunk program of each chunk offset (at 1, 2 and 4
rows), the decode step, and the engine's small cache and sampling
programs through a short drive of the engine itself.  Then the window:

* requests are submitted at their arrival times (an open loop at the
  rate the cell fixes), which run for the cell's ``ramp_s`` in set-up
  first, so that the window opens on the traffic's steady state; the
  window's own requests fall due from then on;
* each ``engine.step()`` is timed on the host clock; a token's time is
  the time ``step()`` returned with it appended;
* ``out_tok_s`` counts the tokens that came in the window, over the
  window; ``ttft_p95_ms`` is over every request due in the window, timed
  from its due time; ``itl_p95_ms`` over every gap between consecutive
  tokens of a request that ended in the window.

After the window the engine runs on, taking no new requests, until every
request due in the window has its first token.  Then a sample of the
finished requests, drawn from the seed and holding the longest, goes to
the plain reference (``check.serve_numbers``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import numpy as np

from bench import traffic as traffic_lib

DRAIN_LIMIT_S = 60.0
CHUNK_GROUPS = (1, 2, 4)


@dataclasses.dataclass
class Flight:
    spec: traffic_lib.Req
    req: object                    # the engine's Request
    due: float
    times: list = dataclasses.field(default_factory=list)


def reachable_shapes(eng: dict, traffic: dict, engine) -> tuple[set, set]:
    """Whole-prompt buckets and chunk offsets the traffic can reach."""
    c = eng["prefill_chunk"]
    lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    buckets = {engine._bucket_len(n) for n in range(lo, min(hi, c) + 1)}
    offsets = set(range(0, hi, c)) if hi > c else set()
    return buckets, offsets


def warm_up(engine, eng: dict, traffic: dict, vocab: int) -> None:
    """Compile every program of the cell's traffic before the window."""
    import jax.numpy as jnp
    p = engine.params
    buckets, offsets = reachable_shapes(eng, traffic, engine)
    for b in sorted(buckets):
        logits, page = engine._prefill(
            p, {"tokens": jnp.asarray(np.zeros((b,), np.int32),
                                      jnp.int32)[None, :]},
            engine._blank_page, jnp.asarray(b - 1, jnp.int32),
            jnp.asarray(np.ones((1, b), np.float32)))
        engine._sample_rows(logits, [None])
    c = eng["prefill_chunk"]
    # a step batches at most budget // chunk chunks of one offset
    groups = [g for g in CHUNK_GROUPS if g * c <= max(eng["prefill_budget"], c)]
    for off in sorted(offsets):
        for g in (groups if off > 0 else (1,)):
            pages = [engine._blank_page] * g
            page_in = pages[0] if g == 1 else engine.kv.stack_pages(pages)
            logits, out = engine._chunk_fn(off)(
                p, {"tokens": jnp.asarray(np.zeros((g, c), np.int32))},
                page_in, jnp.asarray(np.full((g,), c - 1, np.int32)),
                jnp.asarray(np.ones((g, c), np.float32)))
            if g > 1:
                engine.kv.split_pages(out, g)
            engine._sample_rows(logits, [None] * g)
    # A short drive through the engine's own loop: cache insert, staging,
    # release, decode and sampling at the pool's width.
    rng = np.random.Generator(np.random.PCG64(0))
    lengths = sorted({traffic["prompt"]["min"], min(c, traffic["prompt"]["max"]),
                      traffic["prompt"]["max"]})
    n = eng["n_slots"]
    for i in range(n):
        plen = lengths[i % len(lengths)]
        engine.submit(rng.integers(1, vocab, plen).astype(np.int32), 2)
    engine.run(max_steps=4 * (traffic["prompt"]["max"] // c + 2) + n)
    engine.reset()


def window_metrics(requests, t0: float, t_end: float):
    """End-to-end serving metrics of a window [t0, t_end].

    ``requests``: (due time, token times) of every request submitted.
    Tokens count where they came inside the window; TTFT runs from the
    due time over every request due in the window; the inter-token gaps
    are every gap between consecutive tokens that ended in the window.
    Returns (metrics, medians and counts, requests due with no token)."""
    due = [(d, ts) for d, ts in requests if t0 <= d < t_end]
    tokens = sum(1 for _, ts in requests for x in ts if t0 <= x <= t_end)
    ttft = [(ts[0] - d) * 1e3 for d, ts in due if ts]
    gaps = [(b - a) * 1e3 for _, ts in requests
            for a, b in zip(ts, ts[1:]) if t0 <= b <= t_end]
    metrics = {"out_tok_s": tokens / (t_end - t0),
               "ttft_p95_ms": traffic_lib.percentile(ttft, 95),
               "itl_p95_ms": traffic_lib.percentile(gaps, 95)}
    medians = {"ttft_p50_ms": traffic_lib.percentile(ttft, 50),
               "itl_p50_ms": traffic_lib.percentile(gaps, 50),
               "requests_due": len(due), "gaps": len(gaps),
               "tokens": tokens}
    return metrics, medians, sum(1 for _, ts in due if not ts)


def peak_bytes() -> int:
    """Peak device bytes in use so far on the fullest chip."""
    import jax
    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run(cell: dict, conf: dict, m: dict, seed: int, seconds: float,
        trace_dir: str | None, t_start: float, rehearsal: bool,
        control: bool = False) -> dict:
    import jax

    from bench import check, model, weights
    from repro.models import lm
    from repro.serve.engine import ServeConfig, ServeEngine

    clock = time.perf_counter
    eng = dict(cell["engine"])
    traffic = dict(cell["traffic"])
    if rehearsal:
        eng.update(cell["rehearsal"].get("engine", {}))
        traffic.update(cell["rehearsal"].get("traffic", {}))
    if m["capacity_factor"] * m["k"] < m["n_experts"]:
        raise ValueError("serve cells route without drops: capacity_factor "
                         "must be at least n_experts / k")
    arch = model.arch(conf)
    cfg = arch.program_config(conf, m, rehearsal)
    params = weights.program_params(arch, m, lm.lm_defs(cfg), seed)
    engine = ServeEngine(params, cfg, ServeConfig(
        max_len=eng["max_len"], n_slots=eng["n_slots"],
        prefill_chunk=eng["prefill_chunk"],
        prefill_budget=eng["prefill_budget"], temperature=0.0,
        seed=seed & 0x7FFFFFFF))
    warm_up(engine, eng, traffic, m["vocab"])
    pool = traffic_lib.requests(traffic, m["vocab"], seed)
    counters = {}
    flights: list[Flight] = []
    live: list[Flight] = []
    nxt = 0

    def submit(due: float) -> None:
        nonlocal nxt
        spec = pool[nxt]
        nxt += 1
        r = engine.submit(spec.prompt, spec.out_len)
        f = Flight(spec, r, due)
        flights.append(f)
        live.append(f)

    def span(name):
        return (jax.profiler.TraceAnnotation(name) if trace_dir
                else contextlib.nullcontext())

    def record(t: float) -> None:
        """Give every live request its new tokens (time t)."""
        for f in list(live):
            n = len(f.req.tokens)
            if n > len(f.times):
                f.times.extend([t] * (n - len(f.times)))
            if f.req.done:
                live.remove(f)

    def arrive(base: float, now: float) -> None:
        """Submit every open-loop request due by ``now``."""
        while nxt < len(pool) and base + pool[nxt].arrival <= now:
            submit(base + pool[nxt].arrival)

    # The traffic runs for ramp_s in set-up, so that the window opens on
    # its steady state with requests in flight.  Arrival times count from
    # the ramp's start; requests due before the window are not the
    # window's requests.
    ramp_s = traffic["arrivals"].get("ramp_s", 0.0)
    base = clock()
    while clock() - base < ramp_s:
        arrive(base, clock())
        if engine.queue or engine.sched.active():
            engine.step()
            record(clock())
        else:
            time.sleep(0.001)
    trace_s = min(seconds, 8.0)
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    # The window opens where the window's requests start to fall due; a
    # ramp step that runs past that point is the window's time, and its
    # tokens are the window's.  A traced run opens it once the profiler
    # runs.
    t0 = base + ramp_s if ramp_s and not trace_dir else clock()
    t_end = t0 + seconds
    backlog_open = {"queued": len(engine.queue),
                    "active": len(engine.sched.active())}
    window = span("bench.window")
    window.__enter__()
    tracing = bool(trace_dir)
    counters["start"] = snapshot(engine, t0)
    while True:
        now = clock()
        if tracing and now >= t0 + trace_s:
            counters["end"] = snapshot(engine, now)
            counters["traced"] = traced_counts(
                engine, flights, counters["start"], counters["end"], m, arch)
            window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
        if now >= t_end:
            break
        with span("bench.submit"):
            arrive(base, now)
        if not (engine.queue or engine.sched.active()):
            if nxt >= len(pool):
                break
            with span("bench.wait"):
                time.sleep(max(0.0, min(base + pool[nxt].arrival, t_end)
                               - clock()))
            continue
        with span("bench.step"):
            engine.step()
        record(clock())
    backlog = {"queued": len(engine.queue),
               "active": len(engine.sched.active())}
    if tracing:
        counters["end"] = snapshot(engine, clock())
        counters["traced"] = traced_counts(
            engine, flights, counters["start"], counters["end"], m, arch)
        window.__exit__(None, None, None)
        jax.profiler.stop_trace()
    # drain: every request due in the window gets its first token
    due = [f for f in flights if t0 <= f.due < t_end]
    t_drain = clock()
    while (any(not f.times for f in due)
           and clock() - t_drain < DRAIN_LIMIT_S
           and (engine.queue or engine.sched.active())):
        engine.step()
        record(clock())
    memory_peak = peak_bytes()
    met, medians, failed = window_metrics(
        [(f.due, f.times) for f in flights], t0, t_end)
    out = {
        "attempted": len(due), "failed": failed,
        "metrics": dict(met, setup_s=t0 - t_start),
        "medians": dict(medians, decode_steps=engine.stats["decode_steps"],
                        backlog_at_open=backlog_open,
                        backlog_at_close=backlog,
                        submitted=len(flights)),
        "memory_peak_bytes": memory_peak,
        "traced": counters.get("traced"),
    }
    done = [f for f in flights if f.req.done]
    rng = np.random.Generator(np.random.PCG64(int(seed) + 1))
    n = min(cell["check"]["requests"], len(done))
    longest = max(range(len(done)), key=lambda i: (
        len(done[i].spec.prompt) + len(done[i].req.tokens))) if done else 0
    picks = ([longest] + [int(i) for i in rng.permutation(len(done))
                          if i != longest][:n - 1]) if done else []
    seqs = [(np.asarray(done[i].spec.prompt, np.int32),
             np.asarray(done[i].req.tokens, np.int32)) for i in picks]
    # free the program's state before the reference takes the chip
    del engine, params, flights, live, due, done
    gc.collect()
    t_ref = clock()
    nums = check.serve_numbers(arch, seqs, m, seed, eng["max_len"], control)
    out["reference_s"] = clock() - t_ref
    limits = cell["check"]["limits"]
    out["checks"] = check.verdict({k: nums[k] for k in limits}, limits)
    out["control"] = {k: v for k, v in nums.items() if k not in limits}
    out["checked_tokens"] = int(sum(len(t) for _, t in seqs))
    return out


def snapshot(engine, t: float) -> dict:
    """The engine's counters at time t."""
    return {"t": t, "step": engine.step_count, "stats": dict(engine.stats)}


def traced_counts(engine, flights, a: dict, b: dict, m: dict,
                  arch) -> dict:
    """What the engine did between two snapshots: decode steps, decode
    rows kept and experts that got rows (from the per-step telemetry,
    summed over layers), prefill tokens and calls, and the model FLOPs of
    the tokens produced (decode) and the prompts completed (prefill)."""
    entries = [e for e in engine.telemetry
               if a["step"] <= e["step"] < b["step"] and "expert_load" in e]
    kept = sum(float(np.sum(e["expert_load"] - e["overflow"]))
               for e in entries)
    touched = sum(float(np.count_nonzero(e["expert_load"]))
                  for e in entries)
    d = {k: b["stats"][k] - a["stats"][k]
         for k in ("decode_steps", "prefill_tokens", "prefill_calls",
                   "slot_steps_active", "overflow_total")}
    f_dec = f_pre = 0.0
    for f in flights:
        plen = len(f.spec.prompt)
        for j, x in enumerate(f.times):
            if not a["t"] <= x <= b["t"]:
                continue
            if j == 0:
                f_pre += sum(arch.token_flops(m, p + 1)
                             for p in range(plen))
            else:
                f_dec += arch.token_flops(m, plen + j)
    return dict(d, seconds=b["t"] - a["t"], decode_kept=kept,
                n_slots=engine.sc.n_slots,
                decode_entries=len(entries), decode_touched=touched,
                flops_decode=f_dec, flops_prefill=f_pre)
