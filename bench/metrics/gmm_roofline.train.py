"""Expert GMM in the train step: the least time for three times the
forward work (forward, and backward for activations and weights) of the
rows kept after capacity drops, with every expert's weights read, over
the device time of all GMM kernel events, recomputation included (%)."""
from bench import flops
from bench.metrics import _kernels


def read(ctx):
    tr, m = ctx["run"].get("traced"), ctx["m"]
    if not tr:
        return None
    layers = ctx["arch"].moe_layers(m)
    rows = (tr["tokens"] * m["k"] * (1.0 - tr["fraction_dropped"])
            * layers)
    experts = tr["steps"] * m["n_experts"] * layers
    least = 3 * flops.least_time(flops.gmm_flops(m, rows),
                                 flops.gmm_bytes(m, rows, experts),
                                 ctx["peak"])
    return _kernels.share(least, ctx["trace"].time_of(*_kernels.GMM))
