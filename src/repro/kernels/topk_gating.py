"""Fused top-k gating kernel (Eqs. 3/5, deterministic part).

One pass over a [T_blk, E] logits tile in VMEM produces the top-k values
and indices via rounds of masked argmax (k <= 8 in every assigned arch)
plus the softmax over the k survivors — fusing what XLA would otherwise
lower as sort + gather + scatter + softmax with four HBM round-trips of the
[T, E] logits.  E is small (<= 384 here) so a whole expert row fits a tile:
a 256x384 f32 tile is 384 KiB of VMEM.

Beyond the k softmaxed winners the kernel can emit ``extra`` additional raw
top values (``topk_gating_full``): the noisy gating path needs the
(k+1)-th noisy logit for the Appendix-A smooth load estimator, and fusing
that extra argmax round is free compared to a second sort.

T need not divide the block: trailing rows are zero-padded and trimmed.

Training: a ``jax.custom_vjp`` scatters the softmax-jacobian cotangent (and
any cotangent on the raw values) back to the winning logit positions —
exactly the VJP of ``lax.top_k`` + ``jax.nn.softmax``, so gradients match
the jnp oracle bit-for-bit up to reduction order.

Noise injection and the load estimator stay outside the kernel (they are
bandwidth-trivial elementwise ops XLA already fuses well); the kernel
covers the sort-like part that XLA lowers poorly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gmm import round_up as _round_up
from repro.kernels import platform

NEG = -1e30


def _lanes(x: jax.Array) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)


def max_lowest_index(work: jax.Array):
    """Row max of [T, E] and the lowest lane holding it, both [T, 1] —
    ``lax.top_k``'s tie order, written out: on the TPU ``jnp.argmax``
    does not promise the first of equal maxima, and ties are the rule
    when the gate starts at zero (Appendix A)."""
    m = jnp.max(work, axis=-1, keepdims=True)
    lane = _lanes(work).astype(jnp.float32)           # E < 2^24: exact
    first = jnp.min(jnp.where(work == m, lane, float(work.shape[-1])),
                    axis=-1, keepdims=True)
    return m, first.astype(jnp.int32)


def _topk_kernel(logits_ref, w_ref, idx_ref, vals_ref, *, k: int, kk: int):
    x = logits_ref[...].astype(jnp.float32)           # [T_blk, E]
    vals = []
    idxs = []
    work = x
    for _ in range(kk):
        m, i = max_lowest_index(work)                # [T_blk, 1] each
        vals.append(m[:, 0])
        idxs.append(i[:, 0])
        work = jnp.where(_lanes(work) == i, NEG, work)
    v = jnp.stack(vals, axis=-1)                      # [T_blk, kk]
    # softmax over the k kept entries (Eq. 3: Softmax(KeepTopK(...)))
    vk = v[:, :k]
    mx = vk[:, 0:1]                                   # top-1 is the max
    p = jnp.exp(vk - mx)
    w_ref[...] = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(
        w_ref.dtype)
    idx_ref[...] = jnp.stack(idxs, axis=-1)
    vals_ref[...] = v


def _topk_raw(logits, k, extra, block_t, interpret):
    t, e = logits.shape
    kk = k + extra
    if kk > e:
        # Real exception, not an assert: `python -O` would strip the check
        # and the kernel would silently pick from out-of-range lanes.
        raise ValueError(
            f"top-k gating needs k + extra <= n_experts: "
            f"k={k} + extra={extra} > E={e}")
    bt = min(block_t, _round_up(t, 8))
    tp = _round_up(t, bt)
    lp = jnp.pad(logits, ((0, tp - t), (0, 0))) if tp != t else logits
    kernel = functools.partial(_topk_kernel, k=k, kk=kk)
    w, idx, vals = pl.pallas_call(
        kernel,
        grid=(tp // bt,),
        in_specs=[pl.BlockSpec((bt, e), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((bt, k), lambda i: (i, 0)),
                   pl.BlockSpec((bt, kk), lambda i: (i, 0)),
                   pl.BlockSpec((bt, kk), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((tp, k), jnp.float32),
                   jax.ShapeDtypeStruct((tp, kk), jnp.int32),
                   jax.ShapeDtypeStruct((tp, kk), jnp.float32)),
        compiler_params=platform.compiler_params(),
        interpret=interpret,
    )(lp)
    if tp != t:
        w, idx, vals = w[:t], idx[:t], vals[:t]
    return w, idx, vals


# NOTE: the custom_vjp boundary must not return integer outputs — under
# lax.scan + remat (the transformer stack) jax linearizes through it and
# instantiates float0 cotangents for int dtypes, which downstream integer
# arithmetic (the dispatch plan's argsort keys) cannot consume.  The
# vjp'd core therefore carries the indices as f32 (E <= 384, exact) and
# the public wrappers cast back to int32 outside the boundary.

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _topk(logits, k, extra, block_t, interpret):
    w, idx, vals = _topk_raw(logits, k, extra, block_t, interpret)
    return w, idx.astype(jnp.float32), vals


def _topk_fwd(logits, k, extra, block_t, interpret):
    w, idx, vals = _topk_raw(logits, k, extra, block_t, interpret)
    # The backward pass needs only logits' static shape/dtype for the
    # scatter target; a zero-row slice carries both without keeping the
    # [T, E] noisy-logits tensor alive as a residual (it matters under the
    # transformer stack's remat budget).
    return (w, idx.astype(jnp.float32), vals), (logits[:0], w, idx)


def _topk_bwd(k, extra, block_t, interpret, res, cts):
    empty, w, idx = res                       # empty: [0, E], logits dtype
    dw, _, dvals = cts                        # index output carries no grad
    # Softmax jacobian over the k kept entries: dv_i = w_i (dw_i - <w, dw>).
    dw = dw.astype(jnp.float32)
    dv = w * (dw - jnp.sum(w * dw, axis=-1, keepdims=True))
    dv_full = dvals.astype(jnp.float32).at[:, :k].add(dv)   # [T, kk]
    t = idx.shape[0]
    dlogits = jnp.zeros((t, empty.shape[1]), jnp.float32).at[
        jnp.arange(t)[:, None], idx].add(dv_full)
    return (dlogits.astype(empty.dtype),)


_topk.defvjp(_topk_fwd, _topk_bwd)


def _topk_int(logits, k, extra, block_t, interpret):
    w, idx_f, vals = _topk(logits, k, extra, block_t, interpret)
    idx = jax.lax.stop_gradient(idx_f).astype(jnp.int32)
    return w, idx, vals


@functools.partial(jax.jit, static_argnames=("k", "extra", "block_t",
                                             "interpret"))
def topk_gating_full(logits: jax.Array, k: int, extra: int = 0, *,
                     block_t: int = 256, interpret: bool | None = None):
    """logits: [T, E] -> (weights [T, k] f32 softmaxed over the top-k,
    indices [T, k+extra] i32, raw top values [T, k+extra] f32)."""
    return _topk_int(logits, k, extra, block_t,
                     platform.interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("k", "block_t", "interpret"))
def topk_gating(logits: jax.Array, k: int, *, block_t: int = 256,
                interpret: bool | None = None):
    """logits: [T, E] -> (weights [T, k] f32, indices [T, k] i32)."""
    w, idx, _ = _topk_int(logits, k, 0, block_t,
                          platform.interpret_mode(interpret))
    return w, idx
