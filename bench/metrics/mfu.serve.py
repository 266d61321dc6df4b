"""Model FLOPs of the prompt and output tokens the engine processed in
the traced window, over the window and the chip's bf16 peak (%)."""


def read(ctx):
    tr = ctx["run"].get("traced")
    if not tr or tr["seconds"] <= 0:
        return None
    fl = tr["flops_decode"] + tr["flops_prefill"]
    return 100.0 * fl / tr["seconds"] / (ctx["chips"]
                                        * ctx["peak"]["bf16_flops"])
