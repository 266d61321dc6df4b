"""Device time of one decode step: the median over the traced window's
executions of the engine's ``jit_decode_step`` program of the union of
its ops on the first device (ms)."""
from bench.metrics import _programs


def read(ctx):
    return _programs.median_ms(ctx["trace"], "jit_decode_step")
