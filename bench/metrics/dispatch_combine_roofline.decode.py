"""Dispatch and combine in the decode step: the token rows they must
move (each slot's token in, its kept expert rows out, and back), at the
HBM bandwidth, over the device time of the dispatch and combine kernel
events in the decode program (%)."""
from bench import flops
from bench.metrics import _kernels


def read(ctx):
    tr, m = ctx["run"].get("traced"), ctx["m"]
    progs = _kernels.decode_program(ctx["trace"])
    if not tr or progs is None or tr["decode_entries"] <= 0:
        return None
    scale = tr["decode_steps"] / tr["decode_entries"]
    tokens = tr["slot_steps_active"] * ctx["arch"].moe_layers(m)
    nbytes = flops.dispatch_combine_bytes(m, tokens,
                                          tr["decode_kept"] * scale)
    least = nbytes / ctx["peak"]["hbm_bytes_per_s"]
    return _kernels.share(least, ctx["trace"].time_of(
        *_kernels.DISPATCH_COMBINE, program=progs[0]))
