"""Grouped-query attention with memory-bounded blockwise (flash-style) softmax.

Training/prefill never materializes the [S, S] score matrix: an outer scan
over query blocks and an inner ``fori_loop`` over key/value blocks maintain
online-softmax statistics.  The inner loop's trip count is *dynamic* — for
causal masks only blocks at or below the diagonal run, and for sliding-window
layers only blocks inside the window run — so the HLO does no wasted
quadratic work (this matters for the §Roofline MODEL_FLOPS ratio).

Decode attends a single query against the KV cache; sliding-window layers
use a ring-buffer cache of size ``window`` so a 500k-context gemma-style
model stores only O(window) per local layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common.param import ParamDef
from repro.models import layers
from repro.sharding import context as ctx_lib

NEG_INF = -1e30


def attention_defs(d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, *, qk_norm: bool, dtype) -> dict:
    defs = {
        "wq": ParamDef((d_model, n_heads, head_dim),
                       ("embed_fsdp", "heads", "head_dim"), dtype=dtype,
                       fan_in=d_model),
        "wk": ParamDef((d_model, n_kv_heads, head_dim),
                       ("embed_fsdp", "kv_heads", "head_dim"), dtype=dtype,
                       fan_in=d_model),
        "wv": ParamDef((d_model, n_kv_heads, head_dim),
                       ("embed_fsdp", "kv_heads", "head_dim"), dtype=dtype,
                       fan_in=d_model),
        "wo": ParamDef((n_heads, head_dim, d_model),
                       ("heads", "head_dim", "embed_fsdp"), dtype=dtype,
                       fan_in=n_heads * head_dim),
    }
    if qk_norm:
        defs["q_norm"] = layers.rmsnorm_defs(head_dim)
        defs["k_norm"] = layers.rmsnorm_defs(head_dim)
    return defs


def _qkv(params, x, positions, *, rope_theta, qk_norm, eps=1e-6):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    if qk_norm:
        q = layers.rmsnorm(params["q_norm"], q, eps)
        k = layers.rmsnorm(params["k_norm"], k, eps)
    q = layers.rope(q, positions, rope_theta)
    k = layers.rope(k, positions, rope_theta)
    return q, k, v


def _kv_range(i: int, nkv: int, q_block: int, kv_block: int, causal: bool,
              window: int) -> tuple[int, int]:
    """Static kv-block range visible to query block i."""
    if causal:
        hi = min(nkv, (i * q_block + q_block + kv_block - 1) // kv_block)
    else:
        hi = nkv
    lo = max(0, (i * q_block + 1 - window) // kv_block) if window > 0 else 0
    return lo, hi


def _q_range(j: int, nq: int, q_block: int, kv_block: int, causal: bool,
             window: int) -> tuple[int, int]:
    """Static q-block range that can see kv block j (inverse of _kv_range)."""
    lo = (j * kv_block) // q_block if causal else 0
    if window > 0:
        hi = min(nq, (j * kv_block + kv_block - 1 + window) // q_block + 1)
    else:
        hi = nq
    return lo, hi


def _mask(pos_q, pos_k, causal, window):
    m = jnp.ones((pos_q.shape[0], pos_k.shape[0]), bool)
    if causal:
        m &= pos_k[None, :] <= pos_q[:, None]
    if window > 0:
        m &= pos_k[None, :] > pos_q[:, None] - window
    return m


def blockwise_attention(q, k, v, *, causal: bool = True, window: int = 0,
                        q_block: int = 512, kv_block: int = 512,
                        kv_len: jax.Array | None = None,
                        q_offset: int = 0):
    """q: [B,Sq,H,hd]; k,v: [B,Skv,KV,hd] -> [B,Sq,H,hd].

    Assumes q position i attends kv positions <= i (+ window lower bound).
    ``kv_len`` optionally masks a padded cache tail.  ``q_offset`` (a
    *static* int) places the queries at absolute positions
    ``q_offset + i`` against kv positions ``0..Skv`` — chunked prefill
    resumes a prompt mid-sequence with the cached prefix as kv context
    while the static per-block kv ranges keep pruning above the shifted
    diagonal.
    """
    b, sq, h, hd = q.shape
    skv, kv_heads = k.shape[1], k.shape[2]
    g = h // kv_heads
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    if sq % q_block != 0 or skv % kv_block != 0:
        raise ValueError(
            f"sequence lengths must divide the attention blocks: "
            f"sq={sq} % q_block={q_block}, skv={skv} % kv_block={kv_block}")
    nq = sq // q_block
    nkv = skv // kv_block
    scale = 1.0 / (hd ** 0.5)
    # [B, KV, G, S, hd] layout so GQA is a plain batched matmul.
    qr = jnp.moveaxis(q.reshape(b, sq, kv_heads, g, hd), 1, 3)
    kr = jnp.moveaxis(k, 1, 3)                     # [B, KV, hd, Skv]
    vr = jnp.moveaxis(v, 1, 2)                     # [B, KV, Skv, hd]

    def one_q_block(i: int):
        # i is a *Python* int: the kv range below is static, so only the
        # blocks at/below the diagonal (and inside the window) exist in the
        # HLO at all — no masked-out quadratic work, and the loop stays
        # reverse-mode differentiable.
        q_i = jax.lax.slice_in_dim(qr, i * q_block, (i + 1) * q_block,
                                   axis=3)
        pos_q = q_offset + i * q_block + jnp.arange(q_block)
        if causal:
            hi = min(nkv, (q_offset + i * q_block + q_block + kv_block - 1)
                     // kv_block)
        else:
            hi = nkv
        lo = max(0, (q_offset + i * q_block + 1 - window) // kv_block) \
            if window > 0 else 0

        def kv_step(carry, j):
            acc, m, l = carry
            k_j = jax.lax.dynamic_slice_in_dim(kr, j * kv_block, kv_block,
                                               axis=3)
            v_j = jax.lax.dynamic_slice_in_dim(vr, j * kv_block, kv_block,
                                               axis=2)
            pos_k = j * kv_block + jnp.arange(kv_block)
            s = jnp.einsum("bkgqh,bkhs->bkgqs", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((q_block, kv_block), bool)
            if causal:
                mask &= pos_k[None, :] <= pos_q[:, None]
            if window > 0:
                mask &= pos_k[None, :] > pos_q[:, None] - window
            if kv_len is not None:
                mask &= (pos_k < kv_len)[None, :]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bksh->bkgqh", p.astype(v_j.dtype), v_j,
                            preferred_element_type=jnp.float32)
            acc_new = acc * alpha[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, kv_heads, g, q_block, hd), jnp.float32)
        m0 = jnp.full((b, kv_heads, g, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv_heads, g, q_block), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                      jnp.arange(lo, hi))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)                 # [B, KV, G, qb, hd]

    blocks = jnp.stack([one_q_block(i) for i in range(nq)], axis=3)
    # blocks: [B, KV, G, nq, qb, hd] -> [B, Sq, H, hd]
    return blocks.reshape(b, kv_heads, g, sq, hd).reshape(
        b, h, sq, hd).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# FlashAttention-2-style memory-bounded attention with a custom VJP.
#
# The naive blockwise backward lets XLA stack one [B,KV,G,qb,kvb] probability
# tensor per kv step as a scan residual — 23 GiB/device of temps for even a
# 135M model at 4k (measured; see EXPERIMENTS.md §Perf iteration 1).  The
# custom VJP saves only (q, k, v, out, logsumexp) and recomputes the
# probabilities blockwise in the backward pass: dq in q-block-major order,
# dk/dv in kv-block-major order, both with static diagonal/window ranges.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(qr, kr, vr, causal, window, q_block, kv_block):
    """qr: [B,KV,G,Sq,hd]; kr: [B,KV,hd,Skv]; vr: [B,KV,Skv,hd]."""
    out, _ = _flash_fwd_impl(qr, kr, vr, causal, window, q_block, kv_block)
    return out


def _flash_fwd_impl(qr, kr, vr, causal, window, q_block, kv_block):
    b, kv_heads, g, sq, hd = qr.shape
    skv = kr.shape[-1]
    nq, nkv = sq // q_block, skv // kv_block
    scale = 1.0 / (hd ** 0.5)
    outs, lses = [], []
    for i in range(nq):
        q_i = jax.lax.slice_in_dim(qr, i * q_block, (i + 1) * q_block,
                                   axis=3)
        pos_q = i * q_block + jnp.arange(q_block)
        lo, hi = _kv_range(i, nkv, q_block, kv_block, causal, window)

        def kv_step(carry, j, q_i=q_i, pos_q=pos_q):
            acc, m, l = carry
            k_j = jax.lax.dynamic_slice_in_dim(kr, j * kv_block, kv_block,
                                               axis=3)
            v_j = jax.lax.dynamic_slice_in_dim(vr, j * kv_block, kv_block,
                                               axis=2)
            pos_k = j * kv_block + jnp.arange(kv_block)
            s = jnp.einsum("bkgqh,bkhs->bkgqs", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(_mask(pos_q, pos_k, causal, window), s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bksh->bkgqh", p.astype(v_j.dtype), v_j,
                            preferred_element_type=jnp.float32)
            return (acc * alpha[..., None] + pv, m_new, l_new), None

        acc0 = jnp.zeros((b, kv_heads, g, q_block, hd), jnp.float32)
        m0 = jnp.full((b, kv_heads, g, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv_heads, g, q_block), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                      jnp.arange(lo, hi))
        lsafe = jnp.maximum(l, 1e-30)
        outs.append((acc / lsafe[..., None]).astype(qr.dtype))
        lses.append(m + jnp.log(lsafe))
    return jnp.concatenate(outs, axis=3), jnp.concatenate(lses, axis=3)


def _flash_fwd(qr, kr, vr, causal, window, q_block, kv_block):
    out, lse = _flash_fwd_impl(qr, kr, vr, causal, window, q_block,
                               kv_block)
    return out, (qr, kr, vr, out, lse)


def _flash_bwd(causal, window, q_block, kv_block, res, dout):
    qr, kr, vr, out, lse = res
    b, kv_heads, g, sq, hd = qr.shape
    skv = kr.shape[-1]
    nq, nkv = sq // q_block, skv // kv_block
    scale = 1.0 / (hd ** 0.5)
    # delta_i = rowsum(dOut * Out)   [B,KV,G,Sq]
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)

    def p_block(i, j, q_i):
        k_j = jax.lax.dynamic_slice_in_dim(kr, j * kv_block, kv_block,
                                           axis=3)
        pos_q = i * q_block + jnp.arange(q_block)
        pos_k_rel = jnp.arange(kv_block)
        s = jnp.einsum("bkgqh,bkhs->bkgqs", q_i, k_j,
                       preferred_element_type=jnp.float32) * scale
        pos_k = j * kv_block + pos_k_rel
        s = jnp.where(_mask(pos_q, pos_k, causal, window), s, NEG_INF)
        lse_i = jax.lax.slice_in_dim(lse, i * q_block, (i + 1) * q_block,
                                     axis=3)
        return jnp.exp(s - lse_i[..., None]), k_j

    # dq: q-block-major (same ranges as forward).
    dqs = []
    for i in range(nq):
        q_i = jax.lax.slice_in_dim(qr, i * q_block, (i + 1) * q_block,
                                   axis=3)
        do_i = jax.lax.slice_in_dim(dout, i * q_block, (i + 1) * q_block,
                                    axis=3).astype(jnp.float32)
        dl_i = jax.lax.slice_in_dim(delta, i * q_block, (i + 1) * q_block,
                                    axis=3)
        lo, hi = _kv_range(i, nkv, q_block, kv_block, causal, window)

        def dq_step(acc, j, i=i, q_i=q_i, do_i=do_i, dl_i=dl_i):
            p, k_j = p_block(i, j, q_i)
            v_j = jax.lax.dynamic_slice_in_dim(vr, j * kv_block, kv_block,
                                               axis=2)
            dp = jnp.einsum("bkgqh,bksh->bkgqs", do_i,
                            v_j.astype(jnp.float32))
            ds = p * (dp - dl_i[..., None]) * scale
            dq = jnp.einsum("bkgqs,bkhs->bkgqh", ds, k_j)
            return acc + dq, None

        acc0 = jnp.zeros((b, kv_heads, g, q_block, hd), jnp.float32)
        dq_i, _ = jax.lax.scan(jax.checkpoint(dq_step), acc0,
                               jnp.arange(lo, hi))
        dqs.append(dq_i.astype(qr.dtype))
    dq = jnp.concatenate(dqs, axis=3)

    # dk/dv: kv-block-major.
    dks, dvs = [], []
    for j in range(nkv):
        k_j = jax.lax.dynamic_slice_in_dim(kr, j * kv_block, kv_block,
                                           axis=3)
        v_j = jax.lax.dynamic_slice_in_dim(vr, j * kv_block, kv_block,
                                           axis=2)
        lo, hi = _q_range(j, nq, q_block, kv_block, causal, window)

        def dkv_step(carry, i, j=j, k_j=k_j, v_j=v_j):
            dk_acc, dv_acc = carry
            q_i = jax.lax.dynamic_slice_in_dim(qr, i * q_block, q_block,
                                               axis=3)
            do_i = jax.lax.dynamic_slice_in_dim(
                dout, i * q_block, q_block, axis=3).astype(jnp.float32)
            dl_i = jax.lax.dynamic_slice_in_dim(delta, i * q_block, q_block,
                                                axis=3)
            pos_q = i * q_block + jnp.arange(q_block)
            pos_k = j * kv_block + jnp.arange(kv_block)
            s = jnp.einsum("bkgqh,bkhs->bkgqs", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(_mask(pos_q, pos_k, causal, window), s, NEG_INF)
            lse_i = jax.lax.dynamic_slice_in_dim(lse, i * q_block, q_block,
                                                 axis=3)
            p = jnp.exp(s - lse_i[..., None])
            dv_acc = dv_acc + jnp.einsum("bkgqs,bkgqh->bksh", p, do_i)
            dp = jnp.einsum("bkgqh,bksh->bkgqs", do_i,
                            v_j.astype(jnp.float32))
            ds = p * (dp - dl_i[..., None]) * scale
            dk_acc = dk_acc + jnp.einsum("bkgqs,bkgqh->bkhs", ds,
                                         q_i.astype(jnp.float32))
            return (dk_acc, dv_acc), None

        dk0 = jnp.zeros((b, kv_heads, hd, kv_block), jnp.float32)
        dv0 = jnp.zeros((b, kv_heads, kv_block, hd), jnp.float32)
        (dk_j, dv_j), _ = jax.lax.scan(jax.checkpoint(dkv_step), (dk0, dv0),
                                       jnp.arange(lo, hi))
        dks.append(dk_j.astype(kr.dtype))
        dvs.append(dv_j.astype(vr.dtype))
    dk = jnp.concatenate(dks, axis=3)
    dv = jnp.concatenate(dvs, axis=2)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd, _flash_bwd)


@jax.named_scope("attention")
def attention(params, x, positions, *, rope_theta: float, qk_norm: bool,
              window: int = 0, q_block: int = 512,
              kv_block: int = 512, pad_heads: int = 0,
              ctx: ctx_lib.MeshContext | None = None) -> jax.Array:
    """Causal self-attention for train/prefill. x: [B, S, d].

    ``pad_heads``: pad query heads (and KV heads, preserving group
    structure) with zeros up to this count so the head axis divides the
    model mesh axis — ~(pad/H)x extra FLOPs instead of TP replication for
    head counts like arctic's 56.  Padded outputs are sliced off before
    the output projection, so the function is numerically unchanged."""
    q, k, v = _qkv(params, x, positions, rope_theta=rope_theta,
                   qk_norm=qk_norm)
    b, sq, h, hd = q.shape
    kv_heads = k.shape[2]
    g = g_orig = h // kv_heads
    if pad_heads > h:
        # Pad the per-group query-head dim (g) so KV heads are untouched:
        # 56 heads (g=7, kv=8) -> 64 (g=8).  Zero heads attend uniformly
        # to garbage that is sliced off below.
        g = -(-pad_heads // kv_heads)
        q = q.reshape(b, sq, kv_heads, g_orig, hd)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, g - g_orig), (0, 0)))
        q = q.reshape(b, sq, kv_heads * g, hd)
        h = kv_heads * g
    q = ctx_lib.with_constraint(q, ("batch", None, "heads", None), ctx)
    k = ctx_lib.with_constraint(k, ("batch", None, "kv_heads", None), ctx)
    v = ctx_lib.with_constraint(v, ("batch", None, "kv_heads", None), ctx)
    q_block = min(q_block, sq)
    kv_block = min(kv_block, sq)
    qr = jnp.moveaxis(q.reshape(b, sq, kv_heads, g, hd), 1, 3)
    kr = jnp.moveaxis(k, 1, 3)
    vr = jnp.moveaxis(v, 1, 2)
    o = flash_attention(qr, kr, vr, True, window, q_block, kv_block)
    o = o[:, :, :g_orig]                      # drop padded heads
    o = o.reshape(b, kv_heads * g_orig, sq, hd).transpose(0, 2, 1, 3)
    dt = x.dtype
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt),
                      preferred_element_type=jnp.float32).astype(dt)


@jax.named_scope("attention")
def prefill_attention(params, x, positions, *, rope_theta: float,
                      qk_norm: bool, cache: dict, window: int = 0,
                      q_block: int = 512, kv_block: int = 512,
                      offset: int | None = None):
    """Prefill: causal attention that also fills the KV cache.

    Returns (y, new_cache).  Full caches take K/V at positions [0, S);
    ring-buffer (windowed) caches take the last ``window`` positions at
    their ``pos % window`` slots.

    ``offset`` (a *static* int) switches to chunked-prefill mode: the S
    tokens are the prompt slice at positions [offset, offset + S), their
    K/V is written into the cache at that range, and attention runs
    against the cached prefix [0, offset) concatenated with the chunk —
    so chunk N resumes exactly where chunk N-1's cache write ended.
    Sliding-window layers are unsupported (their ring buffers make the
    prefix slice ambiguous); the engine refuses chunking for them.
    """
    q, k, v = _qkv(params, x, positions, rope_theta=rope_theta,
                   qk_norm=qk_norm)
    s = x.shape[1]
    length = cache["k"].shape[1]
    kc, vc = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
    if offset is not None:
        if window != 0:
            raise ValueError(
                "chunked prefill is unsupported for sliding-window layers")
        off = int(offset)
        with jax.named_scope("kv_update"):
            new_k = jax.lax.dynamic_update_slice_in_dim(cache["k"], kc, off,
                                                        axis=1)
            new_v = jax.lax.dynamic_update_slice_in_dim(cache["v"], vc, off,
                                                        axis=1)
        # Attend over [cached prefix, this chunk]: the prefix holds the
        # previous chunks' K/V (cast back to compute dtype), the shifted
        # causal mask keeps each row at its absolute position.
        k_ctx = jnp.concatenate(
            [jax.lax.slice_in_dim(cache["k"], 0, off, axis=1)
             .astype(k.dtype), k], axis=1)
        v_ctx = jnp.concatenate(
            [jax.lax.slice_in_dim(cache["v"], 0, off, axis=1)
             .astype(v.dtype), v], axis=1)
        o = blockwise_attention(q, k_ctx, v_ctx, causal=True, window=0,
                                q_block=q_block, kv_block=kv_block,
                                q_offset=off)
        dt = x.dtype
        y = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
        return y, {"k": new_k, "v": new_v}
    o = blockwise_attention(q, k, v, causal=True, window=window,
                            q_block=q_block, kv_block=kv_block)
    with jax.named_scope("kv_update"):
        if window > 0 and s >= length:
            tail = jnp.arange(s - length, s)
            slots = tail % length
            new_k = cache["k"].at[:, slots].set(kc[:, tail])
            new_v = cache["v"].at[:, slots].set(vc[:, tail])
        else:
            new_k = jax.lax.dynamic_update_slice_in_dim(cache["k"], kc, 0,
                                                        axis=1)
            new_v = jax.lax.dynamic_update_slice_in_dim(cache["v"], vc, 0,
                                                        axis=1)
    dt = x.dtype
    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    return y, {"k": new_k, "v": new_v}


# ---------------------------------------------------------------------------
# Decode with KV cache (full or ring-buffer for sliding-window layers)
# ---------------------------------------------------------------------------

def init_cache_defs(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
                    *, window: int = 0, dtype=jnp.bfloat16) -> dict:
    """Cache ParamDefs (zeros).  Sliding-window layers get a ring buffer."""
    length = min(window, max_len) if window > 0 else max_len
    shape = (batch, length, n_kv_heads, head_dim)
    axes = ("batch", "kv_seq", "kv_heads", "head_dim")
    return {"k": ParamDef(shape, axes, init="zeros", dtype=dtype),
            "v": ParamDef(shape, axes, init="zeros", dtype=dtype)}


@jax.named_scope("attention")
def decode_attention(params, x, cache, cur_index, *, rope_theta: float,
                     qk_norm: bool, window: int = 0) -> tuple[jax.Array, dict]:
    """One-token decode. x: [B, 1, d]; cur_index: scalar position, or a
    [B] vector of *per-sequence* positions (continuous-batching slots of
    mixed age each decode at their own offset).

    Returns (y [B,1,d], updated cache).  For windowed layers the cache is a
    ring buffer written at ``cur_index % window``.
    """
    b = x.shape[0]
    cur = jnp.broadcast_to(
        jnp.asarray(cur_index, jnp.int32).reshape(-1), (b,))       # [B]
    positions = cur[:, None]
    q, k_new, v_new = _qkv(params, x, positions, rope_theta=rope_theta,
                           qk_norm=qk_norm)
    length = cache["k"].shape[1]
    slot = cur % length if window > 0 else cur                     # [B]
    # One-hot blend instead of dynamic_update_slice: a DUS at a traced
    # offset on the sharded cache-sequence axis makes GSPMD all-gather the
    # whole cache per layer; the blend is shard-local (each shard compares
    # its own slot ids) and costs one select over data already streamed.
    with jax.named_scope("kv_update"):
        hit = (jnp.arange(length)[None, :] == slot[:, None])[..., None, None]
        k = jnp.where(hit, k_new.astype(cache["k"].dtype), cache["k"])
        v = jnp.where(hit, v_new.astype(cache["v"].dtype), cache["v"])

    h, hd = q.shape[2], q.shape[3]
    kv_heads = k.shape[2]
    g = h // kv_heads
    qr = q.reshape(b, 1, kv_heads, g, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qr, k,
                   preferred_element_type=jnp.float32) / (hd ** 0.5)
    slots = jnp.arange(length)[None, :]                            # [1, S]
    if window > 0:
        # Ring buffer: after writing at `slot`, slot s holds absolute
        # position p = cur - slot + s - W*(s > slot), the latest p <= cur
        # with p % W == s.  All such p lie in (cur - W, cur]; a slot is
        # valid iff it has ever been written, i.e. p >= 0.
        abs_pos = (cur[:, None] - slot[:, None] + slots
                   - length * (slots > slot[:, None]))
        valid = abs_pos >= 0                                       # [B, S]
    else:
        valid = slots <= cur[:, None]                              # [B, S]
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, 1, h, hd).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return y, {"k": k, "v": v}
