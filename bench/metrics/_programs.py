"""What the engine's readers share: the executions of one named program
on the first device and the device time of each, and the first device's
idle time inside the engine's steps.

The serving engine names its compiled programs after their functions
(``jit_decode_step``, ``jit_prefill_chunk``, ``jit_prefill``,
``jit_sample_argmax``); the trace's ``XLA Modules`` line shows each
execution as ``<name>(<fingerprint>)`` and every op carries the
execution that holds it (``Op.program``, ``Op.run``).  A program the
trace does not name (an engine whose programs are all ``jit__lambda``)
has no executions here.
"""
from __future__ import annotations

from bench import trace_reduce

# The harness's span around each engine.step() call: the reduced trace
# keeps only the harness's host spans (the engine's own serve.step covers
# the same call from inside it).
STEP = "bench.step"


def executions(trace, name: str) -> list[float]:
    """Device seconds of each execution of program ``name`` on the first
    device: the union of its ops.  An execution is a run of consecutive
    ops of one (program, run); one cut by the window's edge is left
    out."""
    ops = trace.devices[0] if trace.devices else []
    lo, hi = trace.window
    runs, key = [], None
    for o in ops:
        k = (o.program, o.run)
        if k != key:
            key = k
            named = (isinstance(o.program, str)
                     and o.program.split("(")[0] == name)
            runs.append([named, False, []])
        runs[-1][1] |= o.start <= lo or o.end >= hi
        runs[-1][2].append((o.start, o.end))
    return [trace_reduce.union(iv) for named, cut, iv in runs
            if named and not cut]


def median_ms(trace, name: str):
    """Median device time of the program's executions (ms), or None."""
    t = sorted(executions(trace, name))
    if not t:
        return None
    n = len(t)
    return 1e3 * (t[n // 2] if n % 2 else (t[n // 2 - 1] + t[n // 2]) / 2)


def steps(trace) -> list:
    """The harness's steps inside the window: the union of its
    ``bench.step`` spans, clipped to the window, in time order."""
    lo, hi = trace.window
    return trace_reduce.union_list(
        [(max(s, lo), min(e, hi)) for n, s, e in trace.spans
         if n == STEP and e > lo and s < hi])


def idle_gaps(trace) -> list:
    """The first device's idle intervals inside the window."""
    busy = trace_reduce.union_list(
        [(o.start, o.end) for o in trace.devices[0]])
    edges = [trace.window[0]] + [x for iv in busy for x in iv] + [
        trace.window[1]]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def idle_in_steps(trace) -> list:
    """The first device's idle intervals inside the harness's steps,
    each clipped to its step."""
    st, out, i = steps(trace), [], 0
    for a, b in idle_gaps(trace):
        while i < len(st) and st[i][1] <= a:
            i += 1
        j = i
        while j < len(st) and st[j][0] < b:
            x, y = max(a, st[j][0]), min(b, st[j][1])
            if y > x:
                out.append((x, y))
            j += 1
    return out
