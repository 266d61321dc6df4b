"""Where the Pallas kernels run, decided in one place at call time.

Every kernel entry point takes ``interpret: bool | None = None`` and
resolves it here when it is called, never when a module is imported (that
would initialise a JAX backend — and, on a TPU host, take the chip — as a
side effect of ``import``).  ``None`` means: compile the kernel on a TPU,
run the Pallas interpreter on any other platform (the CPU test host).  An
explicit ``False`` compiles for the TPU wherever the program is lowered —
what the described-topology compile tests do from a CPU host.  Asking for
the interpreter on a TPU is an error: a kernel that silently ran in
interpret mode on the chip would look like a working kernel and measure
like a slow one.

The VMEM budget lives here too: the kernels size their blocks against it
and hand the same number to Mosaic as the kernel's scoped-VMEM limit, so
the estimate and the compiler's limit cannot drift apart.  So do the
chip's peaks, which the GMM's tile rule models its cost with and the
roofline analysis (``launch/mesh.CHIP``) reads.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

# Scoped-VMEM budget per kernel call (v5e's default scoped limit; the
# chip has 128 MiB of VMEM in all).  Passed to Mosaic as
# ``vmem_limit_bytes`` by every pallas_call in this package.
DEFAULT_VMEM_LIMIT = 16 * 1024 * 1024

# The chip the kernels target (TPU v5e): bf16 MXU peak and HBM bandwidth,
# per chip.
PEAK_BF16_FLOPS = 197e12
HBM_BYTES_PER_S = 819e9


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a kernel call's ``interpret`` argument against the platform
    the program runs on (``jax.default_backend()``, read now)."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU: the Pallas kernels compile on this "
            "platform; pass interpret=None (or False)")
    return bool(interpret)


def compiler_params(vmem_limit: int | None = None,
                    **kw) -> pltpu.CompilerParams:
    """Mosaic parameters for one kernel: the VMEM budget (None ->
    DEFAULT_VMEM_LIMIT) as its scoped-VMEM limit, plus any extra fields."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(vmem_limit or DEFAULT_VMEM_LIMIT), **kw)
