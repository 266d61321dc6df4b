"""Expert GMM in the decode step: the least time for the work the decode
calls needed (the routed rows kept, the weights of the experts that got
a row, read once) over the device time of the GMM kernel's events in the
decode program (%)."""
from bench import flops
from bench.metrics import _kernels


def read(ctx):
    tr, m = ctx["run"].get("traced"), ctx["m"]
    progs = _kernels.decode_program(ctx["trace"])
    if not tr or progs is None or tr["decode_entries"] <= 0:
        return None
    kept = tr["decode_kept"]
    # telemetry sums the layers: experts touched per step is the count of
    # experts with a row in some layer, so read their weights per MoE layer
    experts = tr["decode_touched"] * ctx["arch"].moe_layers(m)
    least = flops.least_time(flops.gmm_flops(m, kept),
                             flops.gmm_bytes(m, kept, experts), ctx["peak"])
    least *= tr["decode_steps"] / tr["decode_entries"]
    return _kernels.share(least, ctx["trace"].time_of(*_kernels.GMM,
                                                      program=progs[0]))
