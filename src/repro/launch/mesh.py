"""Production mesh construction.

A function, not a module-level constant: importing this module never touches
jax device state (jax locks the device count on first backend init, and the
smoke tests must see 1 CPU device while the dry-run sees 512 placeholders).

Topology: TPU v5e pods of 256 chips arranged (data=16, model=16); the
multi-pod mesh prepends a ``pod`` axis over the (slower, DCN-connected)
cross-pod dimension.  Axis usage under the default ``dp_tp_ep`` plan:

* ``pod``   — pure data parallelism (gradient sync only; candidate for the
              int8 error-feedback compression in train/compression.py)
* ``data``  — data parallelism + FSDP of parameter d_model dims
* ``model`` — tensor parallelism (heads / d_ff / vocab) and *expert
              parallelism* (the paper's §3.1 model-parallel expert shards)
"""
from __future__ import annotations

import jax

from repro.kernels import platform
from repro.sharding import context as ctx_lib


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return ctx_lib.make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Single-host mesh over however many (possibly fake) devices exist."""
    n = len(jax.devices())
    data = n // model
    return ctx_lib.make_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis.
CHIP = {
    "name": "tpu-v5e",
    "peak_bf16_flops": platform.PEAK_BF16_FLOPS,    # per chip
    "hbm_bandwidth": platform.HBM_BYTES_PER_S,      # bytes/s per chip
    "ici_link_bandwidth": 50e9,     # bytes/s per link
}
