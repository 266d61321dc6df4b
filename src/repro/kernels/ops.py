"""Jit'd public wrappers around the Pallas kernels.

On a TPU these run the compiled kernels; on any other platform they run
in interpret mode (kernel bodies executed with jnp), which is how the
allclose tests against ``ref.py`` validate them.  The choice is made per
call by ``platform.interpret_mode`` — importing this module touches no
JAX backend.

All ops are differentiable (each kernel carries a ``jax.custom_vjp``).
The MeshContext-aware layer lives one level up in
``repro.kernels.backend``: the registry's pallas backend derives the
*per-shard* ``[E_local, C, d]`` view from a ``MeshContext`` and validates
buffers against it before handing the local shapes to these wrappers
(whose kernels pad non-tile-aligned dims internally).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import dispatch as dispatch_lib
from repro.kernels import fused_decode as fused_lib
from repro.kernels import gmm as gmm_lib
from repro.kernels import topk_gating as topk_lib


def gmm(x, w, *, activation: str = "none", bm=None, bn=None, bk=None,
        autotune: bool = True):
    return gmm_lib.gmm(x, w, activation=activation, bm=bm, bn=bn, bk=bk,
                       autotune=autotune)


def expert_ffn(params, x, *, activation: str = "relu",
               bm=None, bn=None, bk=None, autotune: bool = True):
    """Two fused GMMs: up-projection (+act) then down-projection.

    x: [E, C, d]; params carries w1 [E,d,f], w2 [E,f,d], (w3 for swiglu).
    Differentiable end-to-end via the GMM custom VJP.  ``bm/bn/bk`` cap
    the tile walk; left as ``None`` each GMM plans its own operand shapes
    (tuning table, then the tile rule; ``autotune=False`` pins
    ``DEFAULT_TILE`` — see gmm.plan_blocks).
    """
    dt = x.dtype
    w1 = params["w1"].astype(dt)
    w2 = params["w2"].astype(dt)
    blocks = dict(bm=bm, bn=bn, bk=bk, autotune=autotune)
    if activation == "swiglu":
        h = gmm(x, w1, activation="silu", **blocks)
        g = gmm(x, params["w3"].astype(dt), activation="none", **blocks)
        h = (h.astype(jnp.float32) * g.astype(jnp.float32)).astype(dt)
    else:
        h = gmm(x, w1, activation="relu", **blocks)
    return gmm(h, w2, activation="none", **blocks)


def topk_gating(logits, k: int, block_t: int = 256):
    return topk_lib.topk_gating(logits, k, block_t=block_t)


def topk_gating_full(logits, k: int, extra: int = 0, block_t: int = 256):
    """(weights [T,k], indices [T,k+extra], raw top values [T,k+extra]).

    The ``extra`` raw values feed the Appendix-A load estimator (the noisy
    gating path needs the (k+1)-th noisy logit as threshold).
    """
    return topk_lib.topk_gating_full(logits, k, extra, block_t=block_t)


def dispatch(x, eidx, pos, *, n_experts: int, capacity: int,
             vmem_limit: int | None = None, e_block: int | None = None):
    """Fused capacity-buffer build, [T, d] -> [E, C, d].

    ``e_block=None`` auto-selects the buffer regime against the VMEM
    budget (resident when it fits, E-blocked slabs otherwise); raises
    ``DispatchVMEMError`` only when even a one-expert slab exceeds it
    (see kernels/dispatch.py)."""
    return dispatch_lib.dispatch(x, eidx, pos, n_experts=n_experts,
                                 capacity=capacity, vmem_limit=vmem_limit,
                                 e_block=e_block)


def fused_decode_step(x, valid, wg, w1, w2, w3=None, *, k: int,
                      capacity: int, activation: str = "relu",
                      vmem_limit: int | None = None):
    """One fused MoE decode step (routing + scatter + expert FFN +
    combine in a single pallas launch).  Inference-only — no custom VJP;
    see kernels/fused_decode.py.  Returns (y, expert_load, overflow)."""
    return fused_lib.decode_step(x, valid, wg, w1, w2, w3, k=k,
                                 capacity=capacity, activation=activation,
                                 vmem_limit=vmem_limit)


def fused_routed_apply(x, plan_in, plan_out, w1, w2=None, w3=None, *,
                       mode: str = "ffn", activation: str = "relu",
                       out_dtype=None, vmem_limit: int | None = None):
    """Fused dispatch -> grouped matmul(s) -> combine over explicit
    ``DispatchPlan``s (any routing policy; MoA's assignment-major plan
    views included).  Inference-only; see kernels/fused_decode.py."""
    return fused_lib.routed_apply(
        x, plan_in.expert_index, plan_in.position,
        plan_out.expert_index, plan_out.position, plan_out.weight,
        w1, w2, w3, n_experts=plan_in.n_experts,
        capacity=plan_in.capacity, mode=mode, activation=activation,
        out_dtype=out_dtype, vmem_limit=vmem_limit)


def combine(buf, w, eidx, pos, *, out_dtype=None,
            vmem_limit: int | None = None, e_block: int | None = None):
    """Fused weighted combine, [E, C, d] -> [T, d].  Buffer regime as in
    :func:`dispatch`; raises ``DispatchVMEMError`` only when even a
    one-expert slab exceeds the budget (see kernels/dispatch.py)."""
    return dispatch_lib.combine(buf, w, eidx, pos, out_dtype=out_dtype,
                                vmem_limit=vmem_limit, e_block=e_block)
