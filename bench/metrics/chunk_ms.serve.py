"""Device time of one chunked-prefill call: the median over the traced
window's executions of the engine's ``jit_prefill_chunk`` programs
(every chunk offset and group width) of the union of their ops on the
first device (ms)."""
from bench.metrics import _programs


def read(ctx):
    return _programs.median_ms(ctx["trace"], "jit_prefill_chunk")
