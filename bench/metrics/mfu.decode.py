"""Model FLOPs of the output tokens decoded in the traced window, over
the window and the chip's bf16 peak (%): the whole decode step's share,
which bounds what a decode kernel's roofline share can buy."""


def read(ctx):
    tr = ctx["run"].get("traced")
    if not tr or tr["seconds"] <= 0 or tr["flops_decode"] <= 0:
        return None
    return 100.0 * tr["flops_decode"] / tr["seconds"] / (
        ctx["chips"] * ctx["peak"]["bf16_flops"])
