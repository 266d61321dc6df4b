"""Observability: chrome-trace capture, typed metrics, cost-model replay.

Four layers of coverage:

* ``repro.obs.trace`` unit semantics — span/counter/instant event schema
  (the Perfetto-required keys per phase), nesting containment, numpy
  attr coercion on save, save/load round-trip, the NULL tracer and the
  ambient ``use``/``current`` stack.
* ``repro.obs.metrics`` — counter monotonicity, gauge, fixed-bucket
  histogram percentiles (bounded memory, min/max clamping), registry
  type-collision errors, labelled families, the flat ``stats()`` view.
* Engine integration — a traced ``ServeEngine`` run produces a loadable
  chrome trace with the expected span names while greedy outputs stay
  bit-identical to the untraced run; the telemetry ring stays bounded
  while aggregate instruments keep counting.
* Replay fidelity — the simulator drives the *same* ``Scheduler`` /
  ``RequestQueue`` / ``PrefixCache`` code as the engine, so its
  ``StepDecision`` log and counters must equal a real
  ``log_decisions=True`` run exactly, and a trace-fitted ``CostModel``
  must predict the recorded per-op wall within tolerance; plus
  determinism, policy-comparison, and scale smokes.

Timer-hygiene helpers (``benchmarks/common.py``) are covered at the
bottom: ``pctl`` against ``np.percentile``, ``best_of`` min-selection.
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import param as pm
from repro.configs.base import get_config
from repro.models import lm
from repro.obs import metrics as metrics_lib
from repro.obs import replay as rp
from repro.obs import trace as trace_lib
from repro.serve.engine import ServeConfig, ServeEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# trace: event schema, coercion, save/load, NULL, ambient stack
# ---------------------------------------------------------------------------

def test_span_schema_and_nesting():
    tr = trace_lib.Tracer()
    with tr.span("outer", kind="test"):
        with tr.span("inner", n=3):
            pass
    inner, outer = tr.events          # inner exits (and records) first
    for ev in (inner, outer):
        assert ev["ph"] == "X"
        assert set(ev) >= {"name", "ph", "ts", "dur", "pid", "tid", "args"}
        assert ev["dur"] >= 0 and ev["ts"] >= 0
        assert ev["pid"] == os.getpid()
    assert inner["name"] == "inner" and inner["args"] == {"n": 3}
    assert outer["args"] == {"kind": "test"}
    # containment: inner span lies inside outer's [ts, ts+dur]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-9


def test_counter_and_instant_events():
    tr = trace_lib.Tracer()
    tr.counter("serve.queue", depth=4)
    tr.instant("evicted", page=7)
    cnt, inst = tr.events
    assert cnt["ph"] == "C" and cnt["args"] == {"depth": 4}
    assert inst["ph"] == "i" and inst["s"] == "t"
    assert inst["args"] == {"page": 7}


def test_save_load_roundtrip_and_numpy_coercion(tmp_path):
    path = str(tmp_path / "t.json")
    tr = trace_lib.Tracer(path, process_name="unit")
    with tr.span("op", n=np.int64(5), f=np.float32(0.5),
                 shape=(np.int32(2), 3), arr=np.arange(2)):
        pass
    assert tr.save() == path
    events = trace_lib.load(path)
    # metadata first: Perfetto reads the process_name M event
    assert events[0]["ph"] == "M"
    assert events[0]["args"] == {"name": "unit"}
    (ev,) = [e for e in events if e["ph"] == "X"]
    assert ev["args"] == {"n": 5, "f": 0.5, "shape": [2, 3], "arr": [0, 1]}
    with open(path) as f:
        payload = json.load(f)
    assert payload["displayTimeUnit"] == "ms"
    assert isinstance(payload["traceEvents"], list)
    # bare-array form loads too
    bare = str(tmp_path / "bare.json")
    with open(bare, "w") as f:
        json.dump(events, f)
    assert trace_lib.load(bare) == events


def test_save_requires_path(tmp_path):
    tr = trace_lib.Tracer()
    with pytest.raises(ValueError, match="path"):
        tr.save()
    assert tr.save(str(tmp_path / "explicit.json"))


def test_null_tracer_is_free_and_unsaveable():
    assert trace_lib.NULL.enabled is False
    s1 = trace_lib.NULL.span("a", n=1)
    s2 = trace_lib.NULL.span("b")
    assert s1 is s2                    # shared singleton, no allocation
    with s1:
        pass
    trace_lib.NULL.counter("c", v=1)
    trace_lib.NULL.instant("i")
    assert trace_lib.NULL.events == []
    with pytest.raises(ValueError):
        trace_lib.NULL.save()


def test_clear_keeps_inflight_spans_recording():
    """A span opened before ``clear()`` still lands: spans append to the
    tracer's live event list, which clear() empties in place."""
    tr = trace_lib.Tracer()
    span = tr.span("survivor")
    with span:
        tr.clear()
    assert [e["name"] for e in tr.events] == ["survivor"]


def test_ambient_use_stack_restores_on_exception():
    assert trace_lib.current() is trace_lib.NULL
    tr = trace_lib.Tracer()
    with trace_lib.use(tr):
        assert trace_lib.current() is tr
        inner = trace_lib.Tracer()
        with trace_lib.use(inner):
            assert trace_lib.current() is inner
        assert trace_lib.current() is tr
    assert trace_lib.current() is trace_lib.NULL
    with pytest.raises(RuntimeError):
        with trace_lib.use(tr):
            raise RuntimeError("boom")
    assert trace_lib.current() is trace_lib.NULL


# ---------------------------------------------------------------------------
# metrics: instruments and registry
# ---------------------------------------------------------------------------

def test_counter_monotonic():
    c = metrics_lib.Counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(metrics_lib.MetricError, match="negative"):
        c.inc(-1)
    assert c.value == 3.5


def test_gauge_moves_both_ways():
    g = metrics_lib.Gauge("g")
    g.set(4)
    g.dec()
    g.inc(0.5)
    assert g.value == 3.5


def test_histogram_percentiles_bounded_memory():
    h = metrics_lib.Histogram("h")
    h.observe(10.0)
    # a single sample reports itself at every percentile (min/max clamp)
    assert h.percentile(0) == h.p50 == h.p99 == 10.0
    rs = np.random.RandomState(0)
    samples = rs.uniform(0.1, 1.0, size=2000)
    for v in samples:
        h.observe(v)
    assert h.count == 2001
    assert np.isclose(h.sum, samples.sum() + 10.0)
    assert h.p50 <= h.p95 <= h.p99 <= samples.max() + 10.0
    # geometric buckets: interpolated percentile within bucket resolution
    assert abs(h.p50 - np.percentile(samples, 50)) / np.percentile(
        samples, 50) < 0.3
    # bounded memory: the sample list is never kept
    assert len(h._counts) == len(metrics_lib.DEFAULT_BUCKETS) + 1
    snap = h.snapshot()
    assert snap["kind"] == "histogram" and snap["count"] == 2001
    assert snap["max"] == 10.0


def test_histogram_validation():
    with pytest.raises(metrics_lib.MetricError, match="ascending"):
        metrics_lib.Histogram("bad", buckets=(2.0, 1.0))
    h = metrics_lib.Histogram("h", buckets=(1.0, 2.0, 4.0))
    with pytest.raises(metrics_lib.MetricError):
        h.percentile(101)
    assert h.percentile(50) == 0.0      # empty histogram
    h.observe(3.0)
    assert h.percentile(100) == 3.0     # overflow-side clamp to max


def test_registry_declares_and_rejects_collisions():
    reg = metrics_lib.MetricsRegistry()
    c = reg.counter("requests")
    assert reg.counter("requests") is c          # get-or-create
    with pytest.raises(metrics_lib.MetricError, match="already declared"):
        reg.gauge("requests")
    with pytest.raises(metrics_lib.MetricError, match="already declared"):
        reg.counter("requests", labels=("expert",))
    with pytest.raises(metrics_lib.MetricError, match="unknown"):
        reg.get("nope")
    assert "requests" in reg and "nope" not in reg


def test_registry_labelled_family():
    reg = metrics_lib.MetricsRegistry()
    fam = reg.counter("expert_load", labels=("expert",))
    fam.child(expert=0).inc(3)
    fam.child(expert=1).inc()
    assert fam.child(expert=0).value == 3
    with pytest.raises(metrics_lib.MetricError, match="labels"):
        fam.child(layer=0)
    snap = reg.snapshot()["expert_load"]
    assert snap["kind"] == "family"
    assert snap["children"]["expert_load{expert=0}"]["value"] == 3
    # labelled families are not flattened into the back-compat view
    assert "expert_load" not in reg.stats()


def test_stats_flat_view_keeps_int_types():
    reg = metrics_lib.MetricsRegistry()
    reg.counter("n").inc(6)
    reg.gauge("frac").set(0.25)
    reg.histogram("lat").observe(1.0)
    stats = reg.stats()
    assert stats == {"n": 6, "frac": 0.25}
    assert isinstance(stats["n"], int)           # old `== 6` asserts hold


# ---------------------------------------------------------------------------
# engine integration: trace capture, bit-identity, bounded telemetry
# ---------------------------------------------------------------------------

def _moe_cfg():
    return get_config("kimi-k2-1t-a32b").replace(
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
        vocab_size=64, n_experts=4, moe_k=2, moe_d_ff=32,
        param_dtype=jnp.float32, compute_dtype=jnp.float32,
        q_block=16, kv_block=16, capacity_factor=2.0)


@pytest.fixture(scope="module")
def moe_setup():
    cfg = _moe_cfg()
    params = pm.materialize(lm.lm_defs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _staggered_trace(vocab: int, n: int = 6):
    """Shared 32-token prefix, staggered arrivals: request 0 retires and
    seeds the trie before the rest arrive."""
    rs = np.random.RandomState(3)
    shared = rs.randint(1, vocab, (32,)).astype(np.int32)
    return [(np.concatenate([shared,
                             rs.randint(1, vocab, (8,)).astype(np.int32)]),
             4, 0 if i == 0 else 12 + i) for i in range(n)]


_SERVE_KW = dict(max_len=64, n_slots=4, prefill_chunk=16,
                 prefill_budget=32, admission="aware", prefix_cache=True)


def _run_engine(params, cfg, trace, **kw):
    eng = ServeEngine(params, cfg, ServeConfig(**_SERVE_KW, **kw))
    reqs = [eng.submit(p, m, arrival=a) for p, m, a in trace]
    eng.run()
    assert all(r.done for r in reqs)
    return [r.tokens for r in reqs], eng


def test_traced_run_bit_identical_with_loadable_trace(moe_setup, tmp_path):
    cfg, params = moe_setup
    trace = _staggered_trace(cfg.vocab_size)
    path = str(tmp_path / "serve.json")
    toks_off, _ = _run_engine(params, cfg, trace)
    toks_on, eng = _run_engine(params, cfg, trace, trace_path=path)
    assert toks_on == toks_off                   # tracing is observation
    assert os.path.exists(path)                  # run() saved at trace end
    events = trace_lib.load(path)
    assert events[0]["ph"] == "M"
    names = {e["name"] for e in events}
    assert {"serve.step", "serve.schedule", "serve.prefill_chunk",
            "serve.decode", "serve.sample", "serve.kv_insert",
            "serve.retire", "serve.prefix_probe",
            "serve.queue"} <= names
    for ev in events:
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
            assert set(ev) >= {"name", "ts", "dur", "pid", "tid"}
    # span attrs carry the cost-model regressors
    chunk = next(e for e in events if e["name"] == "serve.prefill_chunk")
    assert chunk["args"]["tokens"] == \
        chunk["args"]["Gp"] * chunk["args"]["C"]
    assert len(events) == len(eng.tracer.events) + 1   # + process_name


# ---------------------------------------------------------------------------
# profiler: the engine's spans and named programs in one jax.profiler trace
# ---------------------------------------------------------------------------

ENGINE_SPANS = {"serve.step", "serve.schedule", "serve.admit",
                "serve.prefix_probe", "serve.inputs", "serve.prefill_chunk",
                "serve.kv_insert", "serve.decode", "serve.sample",
                "serve.sync", "serve.telemetry", "serve.append",
                "serve.retire"}


@pytest.fixture(scope="module")
def profiled(moe_setup, tmp_path_factory):
    """The staggered trace served without a profiler, then again under
    ``jax.profiler`` (Python tracer off): (tokens off, tokens on, the
    host plane's ``serve.*`` events, the ``hlo_module`` names)."""
    import glob

    from jax.profiler import ProfileData
    cfg, params = moe_setup
    trace = _staggered_trace(cfg.vocab_size)
    toks_off, _ = _run_engine(params, cfg, trace)
    d = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        toks_on, _ = _run_engine(params, cfg, trace)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    spans, modules = [], set()
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            for ev in ln.events:
                st = dict(ev.stats)
                if plane.name.startswith("/host") and \
                        ev.name.startswith("serve."):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns, st))
                if "hlo_module" in st:
                    modules.add(st["hlo_module"])
    return toks_off, toks_on, spans, modules


def test_profiler_on_keeps_greedy_tokens(profiled):
    toks_off, toks_on, _, _ = profiled
    assert toks_on == toks_off                   # tracing is observation


def test_profiler_trace_holds_engine_spans_inside_steps(profiled):
    _, _, spans, _ = profiled
    assert ENGINE_SPANS <= {n for n, *_ in spans}
    steps = [(s, e) for n, s, e, _ in spans if n == "serve.step"]
    for name, s, e, _ in spans:
        if name != "serve.step":
            assert any(a <= s and e <= b for a, b in steps), name
    stats = {}
    for name, _, _, st in spans:
        stats.setdefault(name, []).append(st)
    assert sorted(st["step"] for st in stats["serve.step"]) == list(
        range(len(steps)))
    for st in stats["serve.admit"]:
        assert {"rid", "slot", "wait_ms"} <= set(st) and st["wait_ms"] >= 0
    assert sorted(st["rid"] for st in stats["serve.admit"]) == list(range(6))
    for st in stats["serve.prefill_chunk"]:
        assert st["tokens"] == st["Gp"] * st["C"]
    assert all(st["slots"] == 4 for st in stats["serve.decode"])


def test_profiler_trace_names_engine_programs(profiled):
    _, _, _, modules = profiled
    assert {"jit_decode_step", "jit_prefill_chunk",
            "jit_sample_argmax"} <= modules


def test_telemetry_ring_bounded_while_aggregates_count(moe_setup):
    cfg, params = moe_setup
    rs = np.random.RandomState(9)
    eng = ServeEngine(params, cfg, ServeConfig(
        max_len=64, n_slots=2, telemetry_keep_last_n=3))
    for _ in range(2):
        eng.submit(rs.randint(1, cfg.vocab_size, (8,)).astype(np.int32), 8)
    eng.run()
    assert eng.stats["decode_steps"] >= 7    # first token comes from prefill
    assert len(eng.telemetry) == 3               # ring kept only the tail
    assert eng.metrics.get("decode_overflow_per_step").count == \
        eng.stats["decode_steps"]                # aggregates saw every step


# ---------------------------------------------------------------------------
# replay: cost model, fidelity, determinism, scale
# ---------------------------------------------------------------------------

def _synth_events(name, xs, durs_us, xattr):
    return [{"name": name, "ph": "X", "ts": 0.0, "dur": d,
             "args": {xattr: x}} for x, d in zip(xs, durs_us)]


def test_cost_model_fit_recovers_linear_and_constant():
    xs = [1, 2, 4, 8, 16]
    events = _synth_events("serve.decode", xs, [2.0 * x + 5.0 for x in xs],
                           "active")
    events += _synth_events("serve.retire", [1] * 4, [3.0] * 4, "unused")
    model = rp.CostModel.fit(events)
    dec = model.ops["serve.decode"]
    assert np.isclose(dec.a, 2.0e-6) and np.isclose(dec.b, 5.0e-6)
    assert dec.n == 5
    ret = model.ops["serve.retire"]
    assert ret.a == 0.0 and np.isclose(ret.b, 3.0e-6)   # constant fit
    assert model.cost("serve.decode", 10) == pytest.approx(25e-6)
    assert model.cost("never.seen") == 0.0
    rt = rp.CostModel.from_dict(model.to_dict())
    assert rt.ops == model.ops


def test_replay_reproduces_engine_decisions_and_wall(moe_setup, tmp_path):
    """The fidelity contract: same Scheduler/RequestQueue/PrefixCache
    code ⇒ the sim's StepDecision log and counters equal a real
    ``log_decisions=True`` engine run exactly, and the trace-fitted cost
    model predicts the recorded per-op wall within tolerance."""
    cfg, params = moe_setup
    trace = _staggered_trace(cfg.vocab_size, n=8)
    path = str(tmp_path / "fit.json")
    # trace_sync: calibration mode, so span durations are real op walls
    # (what the cost model fits on) rather than async dispatch times.
    eng = ServeEngine(params, cfg, ServeConfig(
        **_SERVE_KW, trace_path=path, log_decisions=True,
        trace_sync=True))
    # warmup pass absorbs jit compiles, then measure a clean run
    for p, m, a in trace:
        eng.submit(p, m, arrival=a)
    eng.run()
    eng.reset()
    eng.tracer.clear()
    reqs = [eng.submit(p, m, arrival=a) for p, m, a in trace]
    eng.run()
    assert all(r.done for r in reqs)
    real_decisions = tuple(eng.sched.decision_log)
    assert real_decisions, "engine logged no decisions"

    model = rp.CostModel.fit_trace(path)
    sim_cfg = rp.ReplayConfig(n_slots=4, admission="aware",
                              prefill_chunk=16, prefill_budget=32,
                              prefix_cache=True, max_len=64)
    res = rp.replay(trace, sim_cfg, model)

    assert tuple(res.decisions) == real_decisions
    for key in ("prefills", "decode_steps", "generated_tokens",
                "slot_steps_active", "slot_steps_total", "prefill_chunks",
                "prefill_tokens", "prefill_calls", "prefix_hits",
                "prefix_hit_tokens"):
        assert res.stats[key] == eng.stats[key], key
    assert [len(r.tokens) for r in res.requests] == \
        [m for _, m, _ in trace]
    assert res.metrics.get("request_latency_steps").count == len(trace)

    # predicted wall vs the recorded time of exactly the ops the sim
    # charges (serve.step would double-count its children; kernel.* spans
    # are compile-time and excluded by the warmup clear above)
    charged = {"serve.schedule", "serve.prefix_probe", "serve.prefix_hit",
               "serve.retire", "serve.prefill", "serve.prefill_chunk",
               "serve.kv_insert", "serve.sample", "serve.decode"}
    recorded = sum(e["dur"] for e in trace_lib.load(path)
                   if e.get("ph") == "X" and e["name"] in charged) / 1e6
    assert recorded > 0
    assert abs(res.predicted_wall_s - recorded) / recorded < 0.10

    # decisions are cost-independent: a zero-cost replay schedules the same
    res0 = rp.replay(trace, sim_cfg, None)
    assert tuple(res0.decisions) == real_decisions
    assert res0.predicted_wall_s == 0.0


def test_replay_deterministic():
    reqs = rp.synthetic_requests(500, prompt_lens=(8, 48), new_tokens=(2, 6),
                                 arrival_every=0.5, shared_prefix=16, seed=4)
    cfg = rp.ReplayConfig(n_slots=4, admission="aware", prefill_chunk=16,
                          prefill_budget=32, prefix_cache=True, max_len=64)
    a = rp.replay(reqs, cfg)
    b = rp.replay(rp.synthetic_requests(500, prompt_lens=(8, 48),
                                        new_tokens=(2, 6), arrival_every=0.5,
                                        shared_prefix=16, seed=4), cfg)
    assert tuple(a.decisions) == tuple(b.decisions)
    assert a.stats == b.stats
    assert a.steps == b.steps


def test_replay_policy_comparison_under_budget_pressure():
    """The simulator's reason to exist: under a tight prefill budget with
    mixed prompt lengths, prompt-length-aware admission beats fcfs on
    tail latency — thousands of requests compared in well under a second
    of host time."""
    reqs = rp.synthetic_requests(2000, prompt_lens=(16, 96),
                                 new_tokens=(4, 8), arrival_every=1.0,
                                 shared_prefix=16, seed=2)
    lat = {}
    for adm in ("fcfs", "aware"):
        cfg = rp.ReplayConfig(n_slots=4, admission=adm, prefill_chunk=16,
                              prefill_budget=16, prefix_cache=True,
                              max_len=128)
        res = rp.replay(reqs, cfg)
        assert res.stats["prefix_hits"] > 0
        lat[adm] = res.metrics.get("request_latency_steps")
    assert lat["aware"].p95 <= lat["fcfs"].p95
    assert lat["aware"].p50 < lat["fcfs"].p50


def test_replay_scale_smoke():
    reqs = rp.synthetic_requests(10_000, prompt_lens=(16, 64),
                                 new_tokens=(2, 8), arrival_every=1.0,
                                 shared_prefix=16, seed=3)
    cfg = rp.ReplayConfig(n_slots=8, admission="aware", prefill_chunk=16,
                          prefill_budget=48, prefix_cache=True, max_len=128)
    t0 = time.perf_counter_ns()
    res = rp.replay(reqs, cfg)
    wall = (time.perf_counter_ns() - t0) / 1e9
    assert res.metrics.get("request_latency_steps").count == 10_000
    assert wall < 30.0, f"10k-request replay took {wall:.1f}s"


@pytest.mark.slow
def test_replay_100k_under_60s():
    """The acceptance bound: 100k requests, two admission policies,
    each under 60s of host wall."""
    reqs = rp.synthetic_requests(100_000, prompt_lens=(16, 192),
                                 new_tokens=(4, 16), arrival_every=1.8,
                                 shared_prefix=64, seed=1)
    for adm in ("fcfs", "aware"):
        cfg = rp.ReplayConfig(n_slots=8, admission=adm, prefill_chunk=32,
                              prefill_budget=32, prefix_cache=True,
                              max_len=256)
        t0 = time.perf_counter_ns()
        res = rp.replay(reqs, cfg)
        wall = (time.perf_counter_ns() - t0) / 1e9
        assert res.metrics.get("request_latency_steps").count == 100_000
        assert wall < 60.0, f"{adm}: {wall:.1f}s"


def test_synthetic_requests_deterministic_shared_prefix():
    a = rp.synthetic_requests(20, shared_prefix=8, seed=7)
    b = rp.synthetic_requests(20, shared_prefix=8, seed=7)
    assert all((pa == pb).all() and ma == mb and aa == ab
               for (pa, ma, aa), (pb, mb, ab) in zip(a, b))
    first = a[0][0][:8]
    assert all((p[:8] == first[:len(p[:8])]).all() for p, _, _ in a)


# ---------------------------------------------------------------------------
# benchmark timer helpers (satellite: shared best-of/percentile discipline)
# ---------------------------------------------------------------------------

def _bench_common():
    sys.path.insert(0, REPO)
    from benchmarks import common
    return common


def test_pctl_matches_numpy():
    common = _bench_common()
    rs = np.random.RandomState(1)
    samples = rs.uniform(0, 100, size=73).tolist()
    for p in (0, 25, 50, 95, 99, 100):
        assert common.pctl(samples, p) == pytest.approx(
            float(np.percentile(samples, p)))
    assert common.pctl([42.0], 95) == 42.0


def test_best_of_picks_min_after_warmup():
    common = _bench_common()
    walls = iter([0.05, 0.3, 0.1, 0.2])          # first is warmup
    runs = []

    def run():
        r = {"wall_s": next(walls), "i": len(runs)}
        runs.append(r)
        return r

    best = common.best_of(run, n=3)
    assert len(runs) == 4                        # warmup + n timed
    assert best["wall_s"] == 0.1                 # warmup's 0.05 excluded
