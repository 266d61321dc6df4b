"""Share of the traced serving window in which the engine's step runs
on the host and no operation runs on the first device (%): the part of
``idle_share.serve`` that the engine's host path holds.  The rest is
the load generator's loop between steps.  The step is the harness's
``bench.step`` span, which wraps each ``engine.step()`` call and is
what the reduced trace keeps of the host plane; the engine's own
``serve.step`` span covers the same call from inside it."""
from bench.metrics import _programs


def read(ctx):
    t = ctx["trace"]
    if t.window_s <= 0 or not t.devices or not _programs.steps(t):
        return None
    idle = sum(b - a for a, b in _programs.idle_in_steps(t))
    return 100.0 * idle / t.window_s
