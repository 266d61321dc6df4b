"""Expert GMM in the prefill programs: the least time for the rows the
prompts' tokens routed (k per token, nothing dropped) and every
expert's weights read once per prefill call, over the device time of
the GMM kernel's events outside the decode program (%)."""
from bench import flops
from bench.metrics import _kernels


def read(ctx):
    tr, m = ctx["run"].get("traced"), ctx["m"]
    progs = _kernels.decode_program(ctx["trace"])
    if not tr or progs is None or not progs[1] or tr["prefill_tokens"] <= 0:
        return None
    layers = ctx["arch"].moe_layers(m)
    rows = tr["prefill_tokens"] * m["k"] * layers
    experts = tr["prefill_calls"] * m["n_experts"] * layers
    least = flops.least_time(flops.gmm_flops(m, rows),
                             flops.gmm_bytes(m, rows, experts), ctx["peak"])
    return _kernels.share(least, ctx["trace"].time_of(*_kernels.GMM,
                                                      program=progs[1]))
