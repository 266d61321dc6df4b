#!/usr/bin/env python3
"""The program's own spans in a profiler trace, beside the device ops.

    python3 bench/spans.py --workload <cell> --seeds <n> [<n> ...] \\
        [--seconds <s>] [--out <dir>]

runs the cell's traced run (as ``bench/run.py --trace 1`` does) once per
seed, keeps each trace under ``--out`` while it is read, and prints one
JSON line per seed: the per-layer metrics, the device-idle time inside
the engine's steps split by the named span that was open (the innermost
one), the steps' device time by program, the compile events inside the
window, and the cost of a span with the profiler off and on.

:func:`read` gives the host events a trace holds of the program and of
JAX as ``(name, start, end, stats)`` in seconds, on the clock of
``trace_reduce.read``'s ops:

* the program's spans (``serve.*``, ``train.*``: ``repro.obs.trace``
  annotations, their attributes as ``stats``);
* JAX's compile events: ``backend_compile_and_load`` (a compile by the
  backend), and ``$compiler.py:<line> log_persistent_cache_hit`` (an
  executable read back from the persistent compile cache instead, which
  leaves no ``backend_compile_and_load``; an event of the profiler's
  Python tracer, which ``jax.profiler`` runs by default).  Both names
  hold on a TPU v5e as on the CPU: a fresh jit inside a traced window
  and a read from the persistent cache count one each there.  The TPU
  trace also shows ``LoadProgramMaybeFromCache`` and
  ``LoadProgramCacheMiss`` around each; they are not counted.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

PREFIXES = ("serve.", "train.")
COMPILE = "backend_compile_and_load"
CACHE_HIT = " log_persistent_cache_hit"


def read(path: str) -> list:
    """The program's spans and JAX's compile events of the trace at
    ``path``.  A second reader of the host plane beside
    ``trace_reduce.read``, which keeps ``bench.*`` spans alone: the next
    ``benchmark`` change folds it into ``trace_reduce`` as a
    ``program_spans`` field of ``Trace`` (PERF.md, section 7)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                n = ev.name
                if (n.startswith(PREFIXES) or n == COMPILE
                        or (n.startswith("$") and n.endswith(CACHE_HIT))):
                    try:
                        st = dict(ev.stats)
                    except Exception:   # noqa: BLE001 — stats unreadable
                        st = {}
                    out.append((n, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9, st))
    return sorted(out, key=lambda s: s[1])


def compiles(spans: list, window) -> dict:
    """Compiles and compile-cache hits that start inside the window."""
    inside = [s for s in spans if window[0] <= s[1] < window[1]]
    return {"backend_compiles": sum(s[0] == COMPILE for s in inside),
            "cache_hits": sum(s[0].endswith(CACHE_HIT) for s in inside)}


def idle_by_span(trace, spans: list) -> dict:
    """Device-idle seconds inside the harness's steps (as
    ``idle_engine.serve`` counts them), each piece given to the innermost
    ``serve.*`` child span of ``serve.step`` open over it ("unnamed"
    where none is)."""
    from bench.metrics import _programs
    kids = sorted((s, e, n) for n, s, e, _ in spans
                  if n.startswith("serve.") and n != "serve.step")
    starts = [k[0] for k in kids]
    longest = max((e - s for s, e, _ in kids), default=0.0)
    by = {}
    for a, b in _programs.idle_in_steps(trace):
        here = [k for k in kids[bisect.bisect_left(starts, a - longest):
                                bisect.bisect_left(starts, b)]
                if k[1] > a]
        cuts = sorted({a, b} | {x for k in here for x in k[:2]
                                if a < x < b})
        for x, y in zip(cuts, cuts[1:]):
            open_ = [k for k in here if k[0] <= x and k[1] >= y]
            name = (min(open_, key=lambda k: k[1] - k[0])[2]
                    if open_ else "unnamed")
            by[name] = by.get(name, 0.0) + (y - x)
    in_steps = sum(by.values())
    return {"window_s": trace.window_s,
            "idle_s": sum(b - a for a, b in _programs.idle_gaps(trace)),
            "idle_in_steps_s": in_steps,
            "named_share": (1 - by.get("unnamed", 0.0) / in_steps
                            if in_steps else None),
            "by_span_s": dict(sorted(by.items(), key=lambda kv: -kv[1]))}


def step_table(trace) -> dict:
    """Each harness step inside the window: its time, the device time of
    each named program whose ops start in it, and the device idle time
    in it; summarised by kind of step (ms, medians and p95)."""
    from bench import trace_reduce as tr
    from bench.metrics import _programs
    lo, hi = trace.window
    ops = trace.devices[0]
    rows = []
    for n, s, e in trace.spans:
        if n != _programs.STEP or s < lo or e > hi:
            continue
        progs = {}
        for o in ops:
            if s <= o.start < e:
                name = str(o.program).split("(")[0]
                progs.setdefault(name, []).append((o.start, min(o.end, e)))
        busy = {k: tr.union(v) for k, v in progs.items()}
        allb = tr.union([iv for v in progs.values() for iv in v])
        rows.append({"step": e - s, "idle": (e - s) - allb, **busy})
    kinds = {"chunk": lambda r: "jit_prefill_chunk" in r,
             "prefill": lambda r: ("jit_prefill" in r
                                   and "jit_prefill_chunk" not in r),
             "decode_only": lambda r: not any(
                 k.startswith("jit_prefill") for k in r)}
    out = {}
    for kind, pick in kinds.items():
        rs = [r for r in rows if pick(r)]
        if not rs:
            continue
        keys = sorted({k for r in rs for k in r})
        out[kind] = {"n": len(rs)}
        for k in keys:
            v = sorted(1e3 * r.get(k, 0.0) for r in rs)
            out[kind][k] = {"median": statistics.median(v),
                            "p95": v[min(len(v) - 1, int(0.95 * len(v)))]}
    return out


def span_cost_us(n: int = 100_000) -> dict:
    """Seconds per span (as µs) of ``trace.NULL.span`` with no profiler
    session and inside one, beside an empty context manager."""
    import jax

    from repro.obs import trace as trace_lib

    class Empty:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    empty = Empty()

    def loop(k, make):
        t = time.perf_counter()
        for _ in range(k):
            with make():
                pass
        return (time.perf_counter() - t) / k * 1e6
    span = trace_lib.NULL.span
    out = {"empty_us": loop(n, lambda: empty),
           "off_us": loop(n, lambda: span("serve.sync", rows=32))}
    d = tempfile.mkdtemp(prefix="span-cost-")
    jax.profiler.start_trace(d)
    try:
        out["on_us"] = loop(n // 10, lambda: span("serve.sync", rows=32))
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--out", default=None,
                    help="where each trace is kept while it is read "
                         "(default: a new temporary directory)")
    args = ap.parse_args()
    import jax

    from bench import run, serve, trace_reduce
    from bench.metrics import _programs
    from repro.common.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        run.log("no TPU found: the benchmark runs on the chip only")
        return 1
    run.log(f"compile cache: {enable_compile_cache()}")
    cost = span_cost_us()
    print(json.dumps({"span_cost": cost}), flush=True)
    # A traced run_cell result holds the per-layer metrics alone; the
    # same run's end-to-end numbers (itl_p95_ms, for the reconciliation
    # of section 5 in PERF.md) are in the serve runner's own result.
    kept = {}
    real = serve.run

    def keep(*a, **kw):
        kept.update(real(*a, **kw))
        return kept
    serve.run = keep
    out = args.out or tempfile.mkdtemp(prefix="bench-spans-")
    os.makedirs(out, exist_ok=True)
    for seed in args.seeds:
        d = os.path.join(out, f"{args.workload}-{seed}")
        kept.clear()
        r = run.run_cell(args.workload, seed, args.seconds, True,
                         trace_dir=d, t_start=time.perf_counter())
        t = trace_reduce.read(trace_reduce.find_xplane(d))
        sp = read(trace_reduce.find_xplane(d))
        per_step = sum(1 for s in sp if s[0].startswith("serve.")
                       and t.window[0] <= s[1] < t.window[1])
        steps = sum(1 for n, s, _ in t.spans if n == _programs.STEP
                    and t.window[0] <= s < t.window[1])
        print(json.dumps({
            "seed": seed, "correct": r["correct"],
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "window": kept.get("metrics"),
            "medians": r["_detail"]["medians"],
            "spans_per_step": per_step / steps if steps else None,
            "idle": idle_by_span(t, sp), "steps": step_table(t),
            "compiles": compiles(sp, t.window),
            "breakdown": r["breakdown"]}), flush=True)
        shutil.rmtree(d, ignore_errors=True)
    if not args.out:
        shutil.rmtree(out, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
