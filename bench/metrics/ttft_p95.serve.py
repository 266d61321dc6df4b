"""95th percentile of time to first token over every request due in the
window, from its due time (ms, host clock).  Over the ~20 requests a
window holds it spreads too widely to bound end to end; it is kept here
to show where chunked prefill and admission stand."""
import math


def read(ctx):
    v = ctx["run"]["metrics"].get("ttft_p95_ms")
    return None if v is None or math.isnan(v) else v
