"""Kernel calls of the train step that left the Pallas kernels for the
reference path, counted by the platform layer after warm-up."""


def read(ctx):
    n = ctx["run"].get("fallbacks")
    return None if n is None else float(n)
