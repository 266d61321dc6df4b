"""Fused dispatch/combine scatter kernels for the capacity-buffer hot path.

``core/dispatch.py`` builds the [E, C, d] expert buffers either with an XLA
scatter (``sort``) or GShard one-hot einsums (``einsum``, O(T·E·C) traffic).
The TPU-native shape is a single kernel pass: the (expert, position) plan
arrays ride in as *scalar-prefetch* operands (SMEM, available before the
body runs — exactly what `PrefetchScalarGridSpec` exists for), the grid
walks blocks of the T·k assignment list, and each step copies token rows
into their slots with dynamic VMEM indexing.  The weighted combine fuses
the gather and the ``sum_k w_k * E_k(x)`` reduction (Eq. 2) in one pass,
accumulating at f32 — the [T, k, d] gathered intermediate of the jnp path
never materializes.

Rows move as 32-bit words.  Mosaic loads and stores one dynamic row of a
VMEM ref only at 32-bit granularity: a bf16 tile packs row pairs into each
32-bit sublane, so a one-row bf16 access cannot be proven aligned and is
refused.  The kernels therefore see 16-bit activations as int32 words
(two adjacent features per word, :func:`to_words`, an XLA bitcast outside
the kernel): dispatch is a pure word copy, bit-exact for every dtype, and
combine splits each word into its two features in f32 (a bf16 value is
the top half of the f32 with the same bits) and emits f32 "planes" —
even and odd features — that are interleaved and cast outside.

Two buffer regimes, selected per call by :func:`select_e_block`:

* **resident** — the destination buffer stays VMEM-resident across the
  whole grid (constant index map — fetched once, a revolving output
  block).  VMEM: the full [E_local, C, d] buffer, e.g. 8 experts x 512
  slots x 512 dims at f32 = 8 MiB, under the 16 MiB budget.
* **E-blocked** — past the budget the expert dimension joins the grid and
  only an [e_block, C, d] slab is live per step.  Assignments are
  pre-bucketed per expert block: every kept assignment owns a unique
  (expert, position) cell, so its bucket slot is just ``e*C + p`` — an
  O(T·k) scatter, no sort — and the bucketed plan rides scalar-prefetch
  like the resident plan does.  This is what keeps paper-scale E on the
  fused path (§3.2's compute-dense experts) instead of bailing to the ref
  scatter.

VMEM accounting (:func:`vmem_bytes` / :func:`eblock_vmem_bytes`) follows
how Pallas allocates on the TPU: a block whose index map moves along the
grid is double-buffered, a block with a constant index map is fetched
once.  The same budget is passed to Mosaic as the kernel's scoped-VMEM
limit (``platform.compiler_params``), so a shape the estimate admits is a
shape the compiler accepts.

Dropped assignments (position >= capacity, including the zero-weight
padding the plan assigns position==capacity) write nothing / combine at
weight 0 — identical semantics to ``core/dispatch.py``.

Both directions carry ``jax.custom_vjp`` so the Pallas path trains:

* dispatch is a (duplicating) copy, so its cotangent is the *unit-weight*
  combine of the output cotangent — the same fused kernel;
* combine's buffer cotangent is ``w_k * dy[t]`` in each kept slot: the
  dispatch copy of ``dy`` scaled by a per-slot weight table, and its
  weight cotangent is the per-assignment dot <dy[t], buf[e, p]>.

The chosen ``e_block`` threads through both VJPs, so forward and backward
run the same buffer regime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import platform
from repro.kernels.gmm import round_up as _round_up
from repro.kernels.platform import DEFAULT_VMEM_LIMIT

# Token-block default for the fused combine.  The backend registry's
# pre-call VMEM estimate and ops.combine's own guard both derive their
# token-block term from THIS constant — one source of truth, so a
# borderline shape cannot pass one guard and trip the other.
COMBINE_BLOCK_T = 64


class DispatchVMEMError(RuntimeError):
    """Fused dispatch/combine buffer exceeds the configured VMEM budget."""


# ---------------------------------------------------------------------------
# 32-bit word views
# ---------------------------------------------------------------------------

def _n_words(d: int, dtype) -> int:
    """32-bit words per d-wide row of ``dtype``."""
    return -(-d * jnp.dtype(dtype).itemsize // 4)


def _n_planes(dtype) -> int:
    """Features per 32-bit word (combine emits one f32 plane per)."""
    return 4 // jnp.dtype(dtype).itemsize


def to_words(x: jax.Array) -> jax.Array:
    """View the trailing dim of ``x`` as 32-bit words.

    32-bit dtypes pass through; 16-bit dtypes pack adjacent feature pairs
    into one int32 (little-endian: feature 2i is the low half), padding an
    odd width with one zero feature."""
    item = jnp.dtype(x.dtype).itemsize
    if item == 4:
        return x
    if item != 2:
        raise TypeError(f"dispatch/combine kernels take 16- or 32-bit rows, "
                        f"got {jnp.dtype(x.dtype).name}")
    if x.shape[-1] % 2:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, 1)])
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    return jax.lax.bitcast_convert_type(pairs, jnp.int32)


def from_words(w: jax.Array, dtype, d: int) -> jax.Array:
    """Inverse of :func:`to_words` (trims the odd-width pad)."""
    if jnp.dtype(dtype).itemsize == 4:
        return w
    x = jax.lax.bitcast_convert_type(w, dtype)          # [..., n, 2]
    return x.reshape(w.shape[:-1] + (-1,))[..., :d]


def _planes(row: jax.Array) -> list:
    """One buffer row of words -> its features as f32 planes: [row] for
    f32 rows; (even, odd) features for packed 16-bit words (a bf16 value
    is the upper half of the f32 with the same bits)."""
    if row.dtype != jnp.int32:
        return [row.astype(jnp.float32)]
    lo = jax.lax.bitcast_convert_type(jnp.left_shift(row, 16), jnp.float32)
    hi = jax.lax.bitcast_convert_type(
        jnp.bitwise_and(row, jnp.int32(-65536)), jnp.float32)
    return [lo, hi]


def _from_planes(planes: list, out_dtype, t: int, d: int) -> jax.Array:
    y = planes[0] if len(planes) == 1 else jnp.stack(planes, -1).reshape(
        planes[0].shape[0], -1)
    return y[:t, :d].astype(out_dtype)


# ---------------------------------------------------------------------------
# VMEM accounting + regime selection
# ---------------------------------------------------------------------------

def _token_bytes(n_tokens: int, d: int, dtype, op: str) -> int:
    """The token-side block: dispatch reads the whole [T, d] row block
    (constant index map: fetched once); combine writes f32 planes per
    token block, which move along the grid (double-buffered)."""
    if op == "dispatch":
        return n_tokens * 4 * _n_words(d, dtype)
    if op == "combine":
        return 2 * n_tokens * 4 * _n_words(d, dtype) * _n_planes(dtype)
    raise ValueError(f"op must be 'dispatch' or 'combine', got {op!r}")


def vmem_bytes(n_experts: int, capacity: int, d: int, dtype,
               n_tokens: int = 0, *, op: str = "dispatch") -> int:
    """VMEM for one *resident-regime* call: the [E, C, d] buffer
    (constant index map — fetched once, never rotated out) plus the
    token-side block (``n_tokens``: all T for dispatch, the token block
    for combine)."""
    return int(n_experts * capacity * 4 * _n_words(d, dtype)
               + _token_bytes(n_tokens, d, dtype, op))


def eblock_vmem_bytes(e_block: int, capacity: int, d: int, dtype,
                      n_tokens: int = 0, *, op: str = "dispatch") -> int:
    """VMEM for one *E-blocked* call: two in-flight [e_block, C, d] slabs
    (the slab's index map moves, so Pallas double-buffers it) plus the
    token-side block."""
    return int(2 * e_block * capacity * 4 * _n_words(d, dtype)
               + _token_bytes(n_tokens, d, dtype, op))


def check_vmem(n_experts: int, capacity: int, d: int, dtype, *,
               n_tokens: int = 0, limit: int | None = None,
               op: str = "dispatch") -> int:
    """Raise DispatchVMEMError when the resident-regime estimate exceeds
    ``limit`` (None -> DEFAULT_VMEM_LIMIT).  Returns the estimate.

    Callers that can run E-blocked should prefer :func:`select_e_block`,
    which picks a slab size instead of raising."""
    limit = DEFAULT_VMEM_LIMIT if limit is None else limit
    need = vmem_bytes(n_experts, capacity, d, dtype, n_tokens, op=op)
    if need > limit:
        raise DispatchVMEMError(
            f"fused dispatch/combine buffer [E={n_experts}, C={capacity}, "
            f"d={d}] ({jnp.dtype(dtype).name}) needs ~{need} B VMEM "
            f"> limit {limit} B; use the E-blocked kernel (e_block / "
            f"select_e_block), shrink capacity, raise the limit, or use "
            f"the ref backend")
    return need


def select_e_block(n_experts: int, capacity: int, d: int, dtype, *,
                   n_tokens: int = 0, limit: int | None = None,
                   op: str = "dispatch") -> int | None:
    """Pick the fused kernels' buffer regime for a shape.

    Returns ``None`` when the whole [E, C, d] buffer fits ``limit``
    (resident-buffer kernels), else the largest power-of-two expert-block
    size whose double-buffered [e_block, C, d] slab pair (plus the token
    block) fits.  Raises :class:`DispatchVMEMError` only when even a
    one-expert slab exceeds the limit.
    """
    limit = DEFAULT_VMEM_LIMIT if limit is None else limit
    if vmem_bytes(n_experts, capacity, d, dtype, n_tokens, op=op) <= limit:
        return None
    blk = 1
    while (blk * 2 < n_experts
           and eblock_vmem_bytes(blk * 2, capacity, d, dtype, n_tokens,
                                 op=op) <= limit):
        blk *= 2
    need = eblock_vmem_bytes(blk, capacity, d, dtype, n_tokens, op=op)
    if need > limit:
        raise DispatchVMEMError(
            f"fused {op} slab [e_block=1, C={capacity}, d={d}] "
            f"({jnp.dtype(dtype).name}, {n_tokens} tokens) needs ~{need} B "
            f"VMEM > limit {limit} B even E-blocked; shrink capacity/d, "
            f"raise the limit, or use the ref backend")
    return blk


# ---------------------------------------------------------------------------
# dispatch: [T, w] -> [E, C, w] word copy
# ---------------------------------------------------------------------------

def _dispatch_kernel(eidx_ref, pos_ref, x_ref, o_ref, *, k: int,
                     capacity: int, block_a: int):
    @pl.when(pl.program_id(0) == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    base = pl.program_id(0) * block_a

    def body(i, carry):
        a = base + i
        p = pos_ref[a]

        # Dropped and padded assignments (p >= capacity) write nothing.
        @pl.when(p < capacity)
        def _copy():
            o_ref[eidx_ref[a], p] = x_ref[a // k]
        return carry

    jax.lax.fori_loop(0, block_a, body, 0)


def _dispatch_raw(x, eidx, pos, n_experts, capacity, block_a, interpret,
                  vmem_limit):
    t, w = x.shape
    k = eidx.shape[1]
    n = t * k
    block_a = min(block_a, n)
    npad = _round_up(n, block_a)
    ef = jnp.zeros((npad,), jnp.int32).at[:n].set(eidx.reshape(-1))
    # Padded assignments get position == capacity => dropped in-kernel.
    pf = jnp.full((npad,), capacity, jnp.int32).at[:n].set(pos.reshape(-1))
    kernel = functools.partial(_dispatch_kernel, k=k, capacity=capacity,
                               block_a=block_a)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(npad // block_a,),
            in_specs=[pl.BlockSpec((t, w), lambda i, *_: (0, 0))],
            out_specs=pl.BlockSpec((n_experts, capacity, w),
                                   lambda i, *_: (0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_experts, capacity, w), x.dtype),
        compiler_params=platform.compiler_params(vmem_limit),
        interpret=interpret,
    )(ef, pf, x)


# ---------------------------------------------------------------------------
# E-blocked dispatch: the grid gains an expert-block dimension; only an
# [e_block, C, w] slab is live per step
# ---------------------------------------------------------------------------

def _bucket_tokens(eidx, pos, n_experts, capacity, e_block):
    """Invert the [T, k] plan into a flat [E_pad * C] slot table:
    ``btok[e*C + p]`` is the token row feeding expert e's slot p, -1 when
    the slot is empty.  Every *kept* assignment owns a unique cell, so no
    sort is needed; dropped assignments (p >= capacity) scatter
    out-of-bounds and are discarded by ``mode="drop"``."""
    t, k = eidx.shape
    e_pad = _round_up(n_experts, e_block)
    pf = pos.reshape(-1)
    slot = jnp.where(pf < capacity, eidx.reshape(-1) * capacity + pf,
                     e_pad * capacity)
    tok = jnp.arange(t * k, dtype=jnp.int32) // k
    return jnp.full((e_pad * capacity,), -1, jnp.int32).at[slot].set(
        tok, mode="drop")


def _dispatch_eblock_kernel(btok_ref, x_ref, o_ref, *, capacity: int,
                            e_block: int):
    base = pl.program_id(0) * (e_block * capacity)

    def body(s, carry):
        tok = btok_ref[base + s]
        row = x_ref[jnp.maximum(tok, 0)]
        # Each output cell is visited exactly once (slots are unique), so
        # empty cells are zeroed here instead of a separate pass.
        o_ref[s // capacity, s % capacity] = jnp.where(tok >= 0, row, 0)
        return carry

    jax.lax.fori_loop(0, e_block * capacity, body, 0)


def _dispatch_eblock_raw(x, eidx, pos, n_experts, capacity, e_block,
                         interpret, vmem_limit):
    t, w = x.shape
    e_pad = _round_up(n_experts, e_block)
    btok = _bucket_tokens(eidx, pos, n_experts, capacity, e_block)
    kernel = functools.partial(_dispatch_eblock_kernel, capacity=capacity,
                               e_block=e_block)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e_pad // e_block,),
            in_specs=[pl.BlockSpec((t, w), lambda b, *_: (0, 0))],
            out_specs=pl.BlockSpec((e_block, capacity, w),
                                   lambda b, *_: (b, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((e_pad, capacity, w), x.dtype),
        compiler_params=platform.compiler_params(vmem_limit),
        interpret=interpret,
    )(btok, x)
    return out[:n_experts] if e_pad != n_experts else out


def _dispatch_any(x, eidx, pos, n_experts, capacity, block_a, e_block,
                  interpret, vmem_limit):
    """[T, d] -> [E, C, d] in x's dtype (bit-exact copy)."""
    words = to_words(x)
    if e_block is None:
        out = _dispatch_raw(words, eidx, pos, n_experts, capacity, block_a,
                            interpret, vmem_limit)
    else:
        out = _dispatch_eblock_raw(words, eidx, pos, n_experts, capacity,
                                   e_block, interpret, vmem_limit)
    return from_words(out, x.dtype, x.shape[-1])


# ---------------------------------------------------------------------------
# combine: [E, C, w] -> [T, d] weighted gather-reduce into f32 planes
# ---------------------------------------------------------------------------

def _combine_kernel(eidx_ref, pos_ref, w_ref, buf_ref, *o_refs, k: int,
                    capacity: int, block_t: int):
    base = pl.program_id(0) * block_t
    n = buf_ref.shape[-1]

    def body(i, carry):
        t = base + i
        acc = [jnp.zeros((n,), jnp.float32) for _ in o_refs]
        for j in range(k):                      # k <= 8: static unroll
            a = t * k + j
            p = pos_ref[a]
            kept = p < capacity
            w = jnp.where(kept, w_ref[a], 0.0)
            row = buf_ref[eidx_ref[a], jnp.where(kept, p, 0)]
            acc = [s + w * v for s, v in zip(acc, _planes(row))]
        for o_ref, s in zip(o_refs, acc):
            o_ref[i] = s
        return carry

    jax.lax.fori_loop(0, block_t, body, 0)


def _combine_eblock_kernel(eidx_ref, pos_ref, w_ref, buf_ref, *o_refs,
                           k: int, capacity: int, block_t: int,
                           e_block: int):
    """Grid (T-blocks, E-blocks), expert dimension innermost: the f32
    output planes stay resident across a token block's slabs and
    accumulate each slab's partial sums."""
    eb = pl.program_id(1)

    @pl.when(eb == 0)
    def _zero():
        for o_ref in o_refs:
            o_ref[...] = jnp.zeros_like(o_ref)

    base_t = pl.program_id(0) * block_t
    base_e = eb * e_block
    n = buf_ref.shape[-1]

    def body(i, carry):
        t = base_t + i
        acc = [jnp.zeros((n,), jnp.float32) for _ in o_refs]
        for j in range(k):                      # k <= 8: static unroll
            a = t * k + j
            e = eidx_ref[a]
            p = pos_ref[a]
            hit = (e >= base_e) & (e < base_e + e_block) & (p < capacity)
            w = jnp.where(hit, w_ref[a], 0.0)
            row = buf_ref[jnp.where(hit, e - base_e, 0),
                          jnp.where(hit, p, 0)]
            acc = [s + w * v for s, v in zip(acc, _planes(row))]
        for o_ref, s in zip(o_refs, acc):
            o_ref[i] = o_ref[i] + s
        return carry

    jax.lax.fori_loop(0, block_t, body, 0)


def _combine_any(buf, w, eidx, pos, out_dtype, block_t, e_block, interpret,
                 vmem_limit):
    n_experts, capacity, d = buf.shape
    words = to_words(buf)
    n = words.shape[-1]
    planes = _n_planes(buf.dtype)
    t, k = eidx.shape
    block_t = min(block_t, t)
    tpad = _round_up(t, block_t)
    npad = tpad * k
    ef = jnp.zeros((npad,), jnp.int32).at[:t * k].set(eidx.reshape(-1))
    pf = jnp.full((npad,), capacity, jnp.int32).at[:t * k].set(
        pos.reshape(-1))
    wf = jnp.zeros((npad,), jnp.float32).at[:t * k].set(
        w.astype(jnp.float32).reshape(-1))
    out_shape = [jax.ShapeDtypeStruct((tpad, n), jnp.float32)] * planes
    if e_block is None:
        kernel = functools.partial(_combine_kernel, k=k, capacity=capacity,
                                   block_t=block_t)
        grid = (tpad // block_t,)
        in_spec = pl.BlockSpec((n_experts, capacity, n),
                               lambda i, *_: (0, 0, 0))
        out_spec = pl.BlockSpec((block_t, n), lambda i, *_: (i, 0))
    else:
        e_pad = _round_up(n_experts, e_block)
        if e_pad != n_experts:
            # Padded experts are never referenced (e < n_experts in the
            # plan), but the slab walk needs a whole number of blocks.
            words = jnp.pad(words, ((0, e_pad - n_experts), (0, 0), (0, 0)))
        kernel = functools.partial(_combine_eblock_kernel, k=k,
                                   capacity=capacity, block_t=block_t,
                                   e_block=e_block)
        # Row-major grid walk: for each token block the expert slabs
        # iterate consecutively over the revolving output block.
        grid = (tpad // block_t, e_pad // e_block)
        in_spec = pl.BlockSpec((e_block, capacity, n),
                               lambda i, j, *_: (j, 0, 0))
        out_spec = pl.BlockSpec((block_t, n), lambda i, j, *_: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid, in_specs=[in_spec],
            out_specs=[out_spec] * planes),
        out_shape=out_shape,
        compiler_params=platform.compiler_params(vmem_limit),
        interpret=interpret,
    )(ef, pf, wf, words)
    return _from_planes(list(out), out_dtype, t, d)


# ---------------------------------------------------------------------------
# differentiable public ops
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _dispatch(x, eidx, pos, n_experts, capacity, block_a, e_block,
              interpret, vmem_limit):
    return _dispatch_any(x, eidx, pos, n_experts, capacity, block_a,
                         e_block, interpret, vmem_limit)


def _dispatch_fwd(x, eidx, pos, n_experts, capacity, block_a, e_block,
                  interpret, vmem_limit):
    return (_dispatch_any(x, eidx, pos, n_experts, capacity, block_a,
                          e_block, interpret, vmem_limit),
            (eidx, pos))


def _dispatch_bwd(n_experts, capacity, block_a, e_block, interpret,
                  vmem_limit, res, g):
    eidx, pos = res
    # The scatter duplicates x[t] into its kept slots, so dx is the
    # unit-weight combine of the cotangent buffer (same fused kernel,
    # same buffer regime).
    unit = jnp.ones(eidx.shape, jnp.float32)
    dx = _combine_any(g, unit, eidx, pos, g.dtype, COMBINE_BLOCK_T,
                      e_block, interpret, vmem_limit)
    return dx, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _combine(buf, w, eidx, pos, out_dtype, block_t, e_block, interpret,
             vmem_limit):
    return _combine_any(buf, w, eidx, pos, out_dtype, block_t, e_block,
                        interpret, vmem_limit)


def _combine_fwd(buf, w, eidx, pos, out_dtype, block_t, e_block, interpret,
                 vmem_limit):
    return (_combine_any(buf, w, eidx, pos, out_dtype, block_t, e_block,
                         interpret, vmem_limit),
            (buf, w, eidx, pos))


def _combine_bwd(out_dtype, block_t, e_block, interpret, vmem_limit, res,
                 g):
    buf, w, eidx, pos = res
    n_experts, capacity, _ = buf.shape
    gf = g.astype(jnp.float32)
    # d_buf[e_k, p_k] = w_k * dy[t]: the dispatch copy of dy (same buffer
    # regime as forward) scaled by the per-slot weight table.
    rows = _dispatch_any(g, eidx, pos, n_experts, capacity, 256, e_block,
                         interpret, vmem_limit)
    wslot = jnp.zeros((n_experts, capacity), jnp.float32).at[
        eidx, pos].set(w.astype(jnp.float32), mode="drop")
    dbuf = (rows.astype(jnp.float32) * wslot[..., None]).astype(buf.dtype)
    # d_w[t, k] = <dy[t], buf[e_k, p_k]> for kept slots (XLA gather: the
    # [T, k, d] intermediate only exists in backward).
    kept = pos < capacity
    gathered = buf[eidx, jnp.clip(pos, 0, capacity - 1)]       # [T, k, d]
    dw = jnp.sum(gf[:, None, :] * gathered.astype(jnp.float32), axis=-1)
    dw = jnp.where(kept, dw, 0.0).astype(w.dtype)
    return dbuf, dw, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def dispatch(x: jax.Array, eidx: jax.Array, pos: jax.Array, *,
             n_experts: int, capacity: int, block_a: int = 256,
             interpret: bool | None = None,
             vmem_limit: int | None = None,
             e_block: int | None = None) -> jax.Array:
    """[T, d] -> [E, C, d]: fused capacity-buffer build (a bit-exact copy).

    ``eidx``/``pos`` are the [T, k] DispatchPlan arrays; assignments with
    ``pos >= capacity`` are dropped, matching ``core.dispatch.dispatch``.
    ``e_block=None`` auto-selects the buffer regime from ``vmem_limit``
    (None -> DEFAULT_VMEM_LIMIT): whole-buffer resident when it fits,
    else the largest fitting E-block slab; an explicit int forces that
    slab size.  Raises :class:`DispatchVMEMError` when even a one-expert
    slab exceeds the limit.  ``interpret`` resolves through
    ``platform.interpret_mode``.
    """
    if e_block is None:
        e_block = select_e_block(n_experts, capacity, x.shape[-1], x.dtype,
                                 n_tokens=x.shape[0], limit=vmem_limit)
    elif e_block < 1:
        raise ValueError(f"e_block must be >= 1, got {e_block}")
    return _dispatch_jit(x, eidx, pos, n_experts, capacity, block_a,
                         e_block, platform.interpret_mode(interpret),
                         vmem_limit or DEFAULT_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _dispatch_jit(x, eidx, pos, n_experts, capacity, block_a, e_block,
                  interpret, vmem_limit):
    return _dispatch(x, eidx, pos, n_experts, capacity, block_a, e_block,
                     interpret, vmem_limit)


def combine(buf: jax.Array, w: jax.Array, eidx: jax.Array, pos: jax.Array,
            *, out_dtype=None, block_t: int = COMBINE_BLOCK_T,
            interpret: bool | None = None,
            vmem_limit: int | None = None,
            e_block: int | None = None) -> jax.Array:
    """[E, C, d] -> [T, d]: fused weighted gather, y = sum_k w_k E_{e_k}(x),
    accumulated in f32 and cast to ``out_dtype`` (None -> buf's dtype).

    ``e_block`` selects the buffer regime exactly as in :func:`dispatch`;
    raises :class:`DispatchVMEMError` when even a one-expert slab exceeds
    ``vmem_limit`` (None -> DEFAULT_VMEM_LIMIT)."""
    out_dtype = out_dtype or buf.dtype
    if e_block is None:
        e_block = select_e_block(
            buf.shape[0], buf.shape[1], buf.shape[2], buf.dtype,
            n_tokens=min(block_t, eidx.shape[0]), limit=vmem_limit,
            op="combine")
    elif e_block < 1:
        raise ValueError(f"e_block must be >= 1, got {e_block}")
    return _combine_jit(buf, w, eidx, pos, out_dtype, block_t, e_block,
                        platform.interpret_mode(interpret),
                        vmem_limit or DEFAULT_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _combine_jit(buf, w, eidx, pos, out_dtype, block_t, e_block, interpret,
                 vmem_limit):
    return _combine(buf, w, eidx, pos, out_dtype, block_t, e_block,
                    interpret, vmem_limit)
