"""The main-path kernels compile for a TPU v5e at the benchmark widths.

Interpret mode never checks tile alignment, Mosaic's lowering rules or
the scoped-VMEM limit, so every kernel is also compiled here for a
*described* v5e chip — no chip attached — at bf16, d_model 7168 and
expert width 2048 (kimi-k2-1t-a32b).  A refusal here is what the chip's
compiler would say.  Each case compiles one kernel alone (a second or
two); nothing runs.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and under pytest-xdist every
worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch as dl
from repro.kernels import fused_decode as fd
from repro.kernels import gmm as gmm_lib
from repro.kernels import topk_gating as topk_lib

D, F = 7168, 2048            # kimi-k2-1t-a32b: d_model, expert width
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # The TPU compiler logs under /tmp unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for(one_chip):
    """compile_for(fn, *(shape, dtype)) -> the compiled executable.

    The persistent compile cache is off meanwhile: an executable for a
    described chip is written to it but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def go(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in specs]
        return jax.jit(fn).lower(*args).compile()
    yield go
    jax.config.update("jax_enable_compilation_cache", prev)


def _kernel_in(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# The expert GMMs of the benchmark cells, (E, C, K, N), tiled by the rule
# (gmm.plan_blocks with nothing in the table): kimi-k2 widths at a
# 512-token prefill chunk and a 32-slot decode step, 16 experts; arctic
# widths (experts 4864 wide) in a 2 x 4096-token train step, 8 experts at
# C = T.  The gradient runs the backward GMMs on their own rule plans.
GMM_SHAPES = {
    "chat-prefill-up": (16, 512, D, F),
    "chat-prefill-down": (16, 512, F, D),
    "chat-decode-up": (16, 32, D, F),
    "chat-decode-down": (16, 32, F, D),
    "train-up": (8, 8192, D, 4864),
    "train-down": (8, 8192, 4864, D),
}


@pytest.mark.parametrize("case", sorted(GMM_SHAPES))
@pytest.mark.parametrize("activation", ["silu", "none"])
def test_gmm_compiles(compile_for, activation, case):
    e, c, k, n = GMM_SHAPES[case]
    assert gmm_lib.plan_blocks(e, c, k, n, BF16).source == "rule"

    def fwd(x, w):
        return gmm_lib.gmm(x, w, activation=activation, interpret=False)

    def loss(x, w):
        return jnp.sum(fwd(x, w).astype(jnp.float32))

    specs = ((e, c, k), BF16), ((e, k, n), BF16)
    # The benchmark's GMM readers match the kernel's instruction by its
    # name less the instance number: `gmm` (bench/trace_reduce.Op.kind).
    kernels = re.findall(r"%(\S+) = [^\n]*tpu_custom_call",
                         compile_for(fwd, *specs).as_text())
    assert kernels and all(re.fullmatch(r"gmm\.\d+", k_) for k_ in kernels)
    assert _kernel_in(compile_for(jax.grad(loss, argnums=(0, 1)), *specs))


# (T tokens, k, E, C): decode (8 slots) and prefill (128-token prompt) of
# the serve phase, and a 2 x 128-token train step.  The regime is what
# select_e_block picks for the shape against the 16 MiB budget.
DISPATCH_SHAPES = {
    "decode-resident": (8, 8, 16, 8),
    "prefill-eblocked": (128, 8, 16, 80),
    "train-eblocked": (256, 8, 8, 320),
}


@pytest.mark.parametrize("case", sorted(DISPATCH_SHAPES))
def test_dispatch_compiles(compile_for, case):
    t, k, e, cap = DISPATCH_SHAPES[case]
    e_block = dl.select_e_block(e, cap, D, BF16, n_tokens=t)
    assert (e_block is None) == case.endswith("resident")
    c = compile_for(
        lambda x, ei, p: dl.dispatch(x, ei, p, n_experts=e, capacity=cap,
                                     e_block=e_block, interpret=False),
        ((t, D), BF16), ((t, k), jnp.int32), ((t, k), jnp.int32))
    assert _kernel_in(c)


@pytest.mark.parametrize("case", sorted(DISPATCH_SHAPES))
def test_combine_compiles(compile_for, case):
    t, k, e, cap = DISPATCH_SHAPES[case]
    e_block = dl.select_e_block(e, cap, D, BF16,
                                n_tokens=min(dl.COMBINE_BLOCK_T, t),
                                op="combine")
    assert (e_block is None) == case.endswith("resident")
    c = compile_for(
        lambda b, w, ei, p: dl.combine(b, w, ei, p, e_block=e_block,
                                       interpret=False),
        ((e, cap, D), BF16), ((t, k), jnp.float32), ((t, k), jnp.int32),
        ((t, k), jnp.int32))
    assert _kernel_in(c)


def test_dispatch_combine_grads_compile(compile_for):
    """The train step differentiates through both kernels: dispatch's
    cotangent is a combine, combine's a dispatch copy."""
    t, k, e, cap = DISPATCH_SHAPES["train-eblocked"]

    def loss(x, w, ei, p):
        buf = dl.dispatch(x, ei, p, n_experts=e, capacity=cap,
                          interpret=False)
        y = dl.combine(buf, w, ei, p, interpret=False)
        return jnp.sum(y.astype(jnp.float32))

    compile_for(jax.grad(loss, argnums=(0, 1)), ((t, D), BF16),
                ((t, k), jnp.float32), ((t, k), jnp.int32),
                ((t, k), jnp.int32))


@pytest.mark.parametrize("t,e,k", [(256, 384, 8), (128, 16, 8)])
def test_topk_gating_compiles(compile_for, t, e, k):
    c = compile_for(
        lambda l: topk_lib.topk_gating_full(l, k, 1, interpret=False),
        ((t, e), jnp.float32))
    assert _kernel_in(c)


# The fused decode kernels hold every expert's weights in VMEM for their
# single grid step.  One expert at d=7168, f=2048 is 88 MB of bf16
# weights, over the 16 MiB budget, so the backend never fuses at these
# widths; they compile here at d=7168 with two 128-wide experts, which is
# inside the budget (fused_decode.decode_vmem_bytes).
FUSED = dict(t=8, e=2, f=128, k=2, cap=8)


def test_fused_decode_step_compiles(compile_for):
    t, e, f, k, cap = (FUSED[n] for n in ("t", "e", "f", "k", "cap"))
    assert fd.decode_vmem_bytes(t, D, f, e, cap, BF16, BF16, gated=True) \
        <= dl.DEFAULT_VMEM_LIMIT
    c = compile_for(
        lambda x, v, wg, w1, w2, w3: fd.decode_step(
            x, v, wg, w1, w2, w3, k=k, capacity=cap, activation="swiglu",
            interpret=False),
        ((t, D), BF16), ((t,), jnp.float32), ((D, e), BF16),
        ((e, D, f), BF16), ((e, f, D), BF16), ((e, D, f), BF16))
    assert _kernel_in(c)


@pytest.mark.parametrize("mode", ["ffn", "proj"])
def test_fused_routed_apply_compiles(compile_for, mode):
    t, e, f, k, cap = (FUSED[n] for n in ("t", "e", "f", "k", "cap"))
    plan = [((t, k), jnp.int32), ((t, k), jnp.int32), ((t, k), jnp.int32),
            ((t, k), jnp.int32), ((t, k), jnp.float32)]
    if mode == "ffn":
        assert fd.routed_vmem_bytes(t, D, D, f, e, cap, BF16, BF16,
                                    gated=True) <= dl.DEFAULT_VMEM_LIMIT
        c = compile_for(
            lambda x, ie, ip, oe, op, ow, w1, w2, w3: fd.routed_apply(
                x, ie, ip, oe, op, ow, w1, w2, w3, n_experts=e,
                capacity=cap, mode="ffn", activation="swiglu",
                interpret=False),
            ((t, D), BF16), *plan, ((e, D, f), BF16), ((e, f, D), BF16),
            ((e, D, f), BF16))
    else:
        c = compile_for(
            lambda x, ie, ip, oe, op, ow, w: fd.routed_apply(
                x, ie, ip, oe, op, ow, w, n_experts=e, capacity=cap,
                mode="proj", interpret=False),
            ((t, D), BF16), *plan, ((e, D, 256), BF16))
    assert _kernel_in(c)
