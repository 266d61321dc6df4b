"""What the per-layer readers share: the kernels' names as the profiler
trace shows them, and which compiled program is the decode step.

The Mosaic kernels appear in the trace under their ``pallas_call`` names
(``%gmm.20 = ... custom-call``: kind ``gmm``; ``_dispatch_jit``,
``_combine_jit``).  The engine's programs are all jitted lambdas, so
they are told apart by fingerprint (``jit__lambda(<n>)``): the decode
step is the program that runs the expert GMM most often (once every
engine step); the other GMM programs are the prefill programs.
"""
from __future__ import annotations

GMM = ("gmm",)
DISPATCH_COMBINE = ("_dispatch_jit", "_combine_jit")


def decode_program(trace):
    """(decode program ids, prefill program ids), or None with no GMM."""
    runs = trace.programs_with(*GMM)
    if not runs:
        return None
    top = max(runs, key=runs.get)
    return {top}, set(runs) - {top}


def share(least_s: float, measured_s: float):
    """A roofline share in %, or None where nothing was measured."""
    if measured_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / measured_s
