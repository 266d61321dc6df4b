"""The program-span readers against hand counts: ``bench/spans.py`` on a
profiler trace recorded here on the CPU (engine-shaped spans around a
named jitted program, under ``bench.window`` / ``bench.step``), and the
per-layer readers ``idle_engine.serve``, ``decode_ms.serve`` and
``chunk_ms.serve`` on synthetic traces."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run, spans, trace_reduce as tr  # noqa: E402
from bench.trace_reduce import Op, Trace  # noqa: E402


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two engine-shaped steps and a fresh compile, profiled on the CPU:
    (path of the trace, its reduction, its program spans)."""
    import jax
    import jax.numpy as jnp

    from repro.obs import trace as trace_lib

    def decode_step(x):
        return jnp.tanh(x @ x)

    fn = jax.jit(decode_step)
    x = jnp.ones((32, 32), jnp.float32)
    fn(x).block_until_ready()
    d = str(tmp_path_factory.mktemp("spans"))
    span = trace_lib.NULL.span
    jax.profiler.start_trace(d)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(2):
                with jax.profiler.TraceAnnotation("bench.step"), \
                        span("serve.step", step=i):
                    with span("serve.decode", active=3):
                        y = fn(x)
                    with span("serve.sample", rows=3), \
                            span("serve.sync", rows=3):
                        np.asarray(y)
            # a program first called inside the window compiles there
            jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = tr.find_xplane(d)
    return path, tr.read(path), spans.read(path)


def test_read_fills_program_spans_from_cpu_trace(recorded):
    _, t, sp = recorded
    names = [n for n, *_ in sp if n.startswith("serve.")]
    assert names.count("serve.step") == 2
    assert {"serve.decode", "serve.sample", "serve.sync"} <= set(names)
    steps = [(s, e, st) for n, s, e, st in sp if n == "serve.step"]
    assert sorted(st["step"] for _, _, st in steps) == [0, 1]
    assert [st for n, *_, st in sp if n == "serve.decode"] == [
        {"active": 3}] * 2
    for n, s, e, _ in sp:
        if n.startswith("serve.") and n != "serve.step":
            assert any(a <= s and e <= b for a, b, _ in steps), n
    # one clock with the reduction: the steps lie inside its window
    for a, b, _ in steps:
        assert t.window[0] <= a and b <= t.window[1]
    # and the engine-shaped spans do not reach the reduction's spans
    assert {n for n, *_ in t.spans} <= {"bench.window", "bench.step"}


def test_compile_events_inside_window(recorded):
    _, t, sp = recorded
    c = spans.compiles(sp, t.window)
    # a backend compile, or an executable read from the persistent cache
    assert c["backend_compiles"] + c["cache_hits"] >= 1
    assert spans.compiles(sp, (0.0, 0.0)) == {"backend_compiles": 0,
                                               "cache_hits": 0}


def _synthetic():
    """Window (0, 10) s; the harness's steps at (1, 4) and (6, 8); the
    device busy at (1.5, 3.5), (6, 7.5) and (9, 9.5)."""
    ops = [Op("fusion.1", 1.5, 2.5, "jit_decode_step(7)", 1),
           Op("gmm.2", 2.5, 3.5, "jit_decode_step(7)", 1),
           Op("gmm.3", 6.0, 7.5, "jit_prefill_chunk(8)", 2),
           Op("fusion.4", 9.0, 9.5, "jit__lambda(9)", 3)]
    host = [("bench.window", 0.0, 10.0), ("bench.step", 1.0, 4.0),
            ("bench.submit", 4.0, 6.0), ("bench.step", 6.0, 8.0)]
    return Trace(devices=[ops], spans=host, window=(0.0, 10.0))


def _read(metric, trace):
    return run.reader(metric).read({"trace": trace})


def test_idle_engine_hand_count():
    # idle inside the steps: (1, 1.5), (3.5, 4) and (7.5, 8) = 1.5 s of 10
    assert _read("idle_engine.serve", _synthetic()) == pytest.approx(15.0)
    t = _synthetic()
    t.spans = [s for s in t.spans if s[0] != "bench.step"]
    assert _read("idle_engine.serve", t) is None
    # a step cut by the window counts inside it only
    t = _synthetic()
    t.window = (2.0, 10.0)
    assert _read("idle_engine.serve", t) == pytest.approx(100 * 1.0 / 8)


def test_program_time_hand_counts():
    ops = [Op("a.1", 0.0, 0.5, "jit_decode_step(7)", 1),   # cut: left out
           Op("a.1", 1.0, 1.2, "jit_decode_step(7)", 2),
           Op("b.2", 1.1, 1.5, "jit_decode_step(7)", 2),   # union 0.5
           Op("c.3", 1.5, 1.6, "jit_sample_argmax(3)", 4),
           Op("a.1", 3.0, 3.4, "jit_decode_step(7)", 5),
           Op("c.3", 3.4, 3.5, "jit_sample_argmax(3)", 6),
           Op("a.1", 5.0, 5.3, "jit_decode_step(7)", 7),
           Op("d.4", 6.0, 6.25, "jit_prefill_chunk(1)", 8),
           Op("d.4", 7.0, 7.75, "jit_prefill_chunk(2)", 9)]
    t = Trace(devices=[ops], spans=[], window=(0.0, 10.0))
    assert _read("decode_ms.serve", t) == pytest.approx(400.0)
    # every chunk offset's program: median of 250 and 750 ms
    assert _read("chunk_ms.serve", t) == pytest.approx(500.0)


def test_program_time_absent_when_programs_are_unnamed():
    ops = [Op("gmm.1", 1.0, 2.0, "jit__lambda(11)", 1),
           Op("gmm.1", 3.0, 4.0, "jit__lambda(12)", 2)]
    t = Trace(devices=[ops], spans=[], window=(0.0, 10.0))
    assert _read("decode_ms.serve", t) is None
    assert _read("chunk_ms.serve", t) is None
    assert _read("decode_ms.serve",
                 Trace(devices=[], spans=[], window=(0.0, 1.0))) is None


def test_idle_split_by_innermost_span():
    t = _synthetic()
    sp = [("serve.step", 1.0, 4.0, {}), ("serve.inputs", 1.0, 1.5, {}),
          ("serve.sample", 3.4, 4.0, {}), ("serve.sync", 3.5, 4.0, {}),
          ("serve.step", 6.0, 8.0, {}), ("serve.decode", 6.0, 7.0, {})]
    got = spans.idle_by_span(t, sp)
    assert got["idle_s"] == pytest.approx(10.0 - 4.0)
    assert got["idle_in_steps_s"] == pytest.approx(1.5)
    assert got["by_span_s"] == pytest.approx(
        {"serve.inputs": 0.5, "serve.sync": 0.5, "unnamed": 0.5})
    assert got["named_share"] == pytest.approx(1.0 / 1.5)


def test_step_table_by_kind():
    got = spans.step_table(_synthetic())
    assert got["decode_only"]["n"] == 1 and got["chunk"]["n"] == 1
    assert got["decode_only"]["jit_decode_step"]["median"] == \
        pytest.approx(2000.0)
    assert got["decode_only"]["idle"]["median"] == pytest.approx(1000.0)
    assert got["chunk"]["step"]["median"] == pytest.approx(2000.0)
    assert got["chunk"]["jit_prefill_chunk"]["median"] == \
        pytest.approx(1500.0)
