"""Model FLOPs of the prompts completed in the traced window, over the
window and the chip's bf16 peak (%): the whole prefill's share, which
bounds what a prefill kernel's roofline share can buy."""


def read(ctx):
    tr = ctx["run"].get("traced")
    if not tr or tr["seconds"] <= 0 or tr["flops_prefill"] <= 0:
        return None
    return 100.0 * tr["flops_prefill"] / tr["seconds"] / (
        ctx["chips"] * ctx["peak"]["bf16_flops"])
