"""Forward and backward model FLOPs of the tokens trained in the traced
window (recomputation not counted), over the window, the chips and the
bf16 peak (%)."""
from bench import flops


def read(ctx):
    tr = ctx["run"].get("traced")
    if not tr or tr["seconds"] <= 0:
        return None
    fl = flops.train_flops(ctx["arch"], ctx["m"], ctx["run"]["seq_len"],
                           tr["tokens"])
    return 100.0 * fl / tr["seconds"] / (ctx["chips"]
                                        * ctx["peak"]["bf16_flops"])
