"""Fused single-launch MoE decode step.

At serve-time decode the MoE hot path runs once per layer per token batch
of B slot rows — and the unfused pipeline pays >= 4 kernel launches for
it (top-k gating, dispatch scatter, expert GMM x2, weighted combine).
This module fuses the whole layer into ONE ``pallas_call``:

* :func:`decode_step` — the full fusion for the ``noisy_topk`` eval path
  (the serve decode default): in-kernel clean-logit routing (Eqs. 3/5,
  deterministic part), capacity-slot assignment (the exact
  ``core.dispatch.plan`` non-priority semantics, computed as a running
  per-expert count in SMEM instead of a sort), the scatter into the
  [E, C, d] capacity buffer, the per-expert FFN (§3.2 one-hidden-layer
  ReLU, or gated-SiLU), and the weighted combine — plus the serving
  telemetry counters (``route_telemetry``'s load/overflow) as extra
  outputs, so the fused layer emits the same counter families the unfused
  path does.
* :func:`routed_apply` — the plan-mode fusion: routing happens outside
  (any registered policy — expert_choice's batch-global column top-k
  cannot be computed per-token in-kernel) and the kernel fuses
  dispatch -> grouped matmul(s) -> combine over explicit in/out plan
  views.  MoA's assignment-major [T·k, 1] plans run through the same
  kernel (``mode="proj"``), so routed-attention decode gets the
  single-launch win for each of its Q/O projections too.

Mosaic constraints shape the kernel body: every dynamic index goes to a
*ref* (the plan lives in SMEM — scalar-prefetched for ``routed_apply``,
written by the routing stage for ``decode_step``), and rows that are
moved one at a time live in f32 VMEM scratch (a one-row access into a
packed bf16 tile cannot be proven aligned).  Values keep their
activation-dtype rounding: the scatter copies bf16 values exactly, the
FFN output is rounded to the activation dtype before it is stored.

Inference-only: no custom VJP — the train path keeps the individually
differentiable kernels.  Everything (weights included) is VMEM-resident
for the one grid step, which is the right regime for decode shapes
(B <= slot-pool size, C = O(B·k/E)) of small experts; :func:`
decode_vmem_bytes` / :func:`routed_vmem_bytes` estimate the footprint so
the backend can fall back loudly past the budget.  At the published
widths of the registered configs one expert's weights alone exceed the
16 MiB budget, so there the backend takes the unfused pipeline.

Numerics: the kernel runs the unfused pipeline's math (same dots with
``preferred_element_type=jnp.float32``, same cast points, ascending-k f32
combine, lowest-index top-k tie-breaking), but it is a different program
and the compiler may order or fuse its float operations differently, so
fused and unfused outputs agree to float tolerance, not bit for bit
(tests/test_fused_decode.py states each tolerance).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import platform
from repro.kernels import topk_gating as topk_lib

NEG = -1e30


# ---------------------------------------------------------------------------
# VMEM estimates (the backend's fallback guard).  grid=(1,): every block
# has a constant index map, so Pallas fetches each one once.
# ---------------------------------------------------------------------------

def decode_vmem_bytes(t: int, d: int, f: int, n_experts: int,
                      capacity: int, x_dtype, w_dtype, *,
                      gated: bool = False) -> int:
    """Estimated VMEM for one fully-fused decode step: the expert weights
    (w1/w2 and w3 when gated) and gate matrix, the f32 [E, C, d] scatter
    and FFN-output scratch, the per-expert f32 hidden tiles, and the token
    blocks (x/y plus their f32 staging)."""
    xi = jnp.dtype(x_dtype).itemsize
    wi = jnp.dtype(w_dtype).itemsize
    e = n_experts
    bufs = 2 * e * capacity * d * 4             # scatter-in + FFN-out, f32
    hidden = 3 * capacity * f * 4               # h, gate and product tiles
    weights = (3 if gated else 2) * e * d * f * wi + d * e * wi
    tokens = 2 * t * d * (xi + 4) + 4 * t * max(e, 128) * 4
    return int(bufs + hidden + weights + tokens)


def routed_vmem_bytes(t: int, d_in: int, d_out: int, f: int,
                      n_experts: int, capacity: int, x_dtype, w_dtype, *,
                      mode: str = "ffn", gated: bool = False) -> int:
    """Estimated VMEM for one plan-mode fused call (``routed_apply``)."""
    xi = jnp.dtype(x_dtype).itemsize
    wi = jnp.dtype(w_dtype).itemsize
    e = n_experts
    bufs = e * capacity * (d_in + d_out) * 4
    if mode == "ffn":
        weights = (3 if gated else 2) * e * d_in * f * wi
        hidden = 3 * capacity * f * 4
    else:
        weights = e * d_in * d_out * wi
        hidden = 0
    tokens = t * (d_in + d_out) * (xi + 4)
    return int(bufs + hidden + weights + tokens)


# ---------------------------------------------------------------------------
# shared in-kernel stages (plan in SMEM refs, rows in f32 VMEM scratch)
# ---------------------------------------------------------------------------

def _scatter_into(buf_ref, xs_ref, e_ref, p_ref, *, n: int, k: int,
                  capacity: int):
    """The dispatch scatter: row a//k of the staged tokens lands in buffer
    cell (e_ref[a], p_ref[a]); dropped assignments (p >= capacity) write
    nothing."""
    buf_ref[...] = jnp.zeros_like(buf_ref)

    def body(a, carry):
        p = p_ref[a]

        @pl.when(p < capacity)
        def _copy():
            buf_ref[e_ref[a], p] = xs_ref[a // k]
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _expert_ffn_into(out_ref, buf_ref, w1_ref, w2_ref, w3_ref, *,
                     n_experts: int, activation: str, dt):
    """Per-expert FFN over the capacity buffers with the unfused
    ``ops.expert_ffn`` math: dt operands into f32-accumulating dots,
    activation in f32, casts at the same points (gmm applies silu before
    its output cast; the swiglu product happens in f32)."""

    def body(ei, carry):
        be = buf_ref[ei].astype(dt)                            # [C, d_in]
        h = jnp.dot(be, w1_ref[ei].astype(dt),
                    preferred_element_type=jnp.float32)
        if activation == "swiglu":
            s = jax.nn.silu(h).astype(dt)
            g = jnp.dot(be, w3_ref[ei].astype(dt),
                        preferred_element_type=jnp.float32).astype(dt)
            h = (s.astype(jnp.float32) * g.astype(jnp.float32)).astype(dt)
        else:
            h = jax.nn.relu(h).astype(dt)
        out = jnp.dot(h, w2_ref[ei].astype(dt),
                      preferred_element_type=jnp.float32)
        out_ref[ei] = out.astype(dt).astype(jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_experts, body, 0)


def _proj_into(out_ref, buf_ref, w_ref, *, n_experts: int, dt):
    """Single grouped matmul (the MoA routed Q/O projection), mirroring
    ``ops.gmm`` with ``activation="none"``."""

    def body(ei, carry):
        out = jnp.dot(buf_ref[ei].astype(dt), w_ref[ei].astype(dt),
                      preferred_element_type=jnp.float32)
        out_ref[ei] = out.astype(dt).astype(jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_experts, body, 0)


def _combine_rows(y_ref, acc_ref, out_ref, e_ref, p_ref, w_ref, *, k: int,
                  capacity: int):
    """The weighted gather-reduce: y[t] = sum_j w_j * out[e_j, p_j],
    accumulated in f32 in ascending-j order into the f32 row scratch,
    then cast to y's dtype in one block store."""

    def body(i, carry):
        acc = jnp.zeros(acc_ref.shape[-1:], jnp.float32)
        for j in range(k):                      # k <= 8: static unroll
            a = i * k + j
            p = p_ref[a]
            kept = p < capacity
            w = jnp.where(kept, w_ref[a], 0.0)
            acc = acc + w * out_ref[e_ref[a], jnp.where(kept, p, 0)]
        acc_ref[i] = acc
        return carry

    jax.lax.fori_loop(0, acc_ref.shape[0], body, 0)
    y_ref[...] = acc_ref[...].astype(y_ref.dtype)


# ---------------------------------------------------------------------------
# the fully-fused decode step (noisy_topk eval routing in-kernel)
# ---------------------------------------------------------------------------

def _decode_kernel(*refs, k: int, capacity: int, activation: str):
    if activation == "swiglu":
        (x_ref, valid_ref, wg_ref, w1_ref, w2_ref, w3_ref,
         y_ref, load_ref, over_ref,
         xs_ref, buf_ref, out_ref, acc_ref,
         e_ref, p_ref, w_ref, cnt_ref) = refs
    else:
        (x_ref, valid_ref, wg_ref, w1_ref, w2_ref,
         y_ref, load_ref, over_ref,
         xs_ref, buf_ref, out_ref, acc_ref,
         e_ref, p_ref, w_ref, cnt_ref) = refs
        w3_ref = None

    xs_ref[...] = x_ref[...].astype(jnp.float32)              # [T, d]
    xf = xs_ref[...]
    t = xf.shape[0]
    wg = wg_ref[...].astype(jnp.float32)                       # [d, E]
    e = wg.shape[-1]

    # --- routing: Eqs. (3)/(5), eval path (clean logits, no noise).
    # Rounds of masked max — same algorithm and lowest-index
    # tie-breaking as the fused top-k gating kernel / lax.top_k.
    logits = jnp.dot(xf, wg, preferred_element_type=jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (t, e), 1)
    work = logits
    vals, idxs = [], []
    for _ in range(k):
        m, i = topk_lib.max_lowest_index(work)                # [T, 1]
        vals.append(m)
        idxs.append(i.astype(jnp.float32))        # E <= 2^24: exact in f32
        work = jnp.where(lane == i, NEG, work)
    probs = [jnp.exp(v - vals[0]) for v in vals]               # top-1 = max
    total = functools.reduce(jnp.add, probs)
    valid = valid_ref[...]                                     # [T, 1]
    weights = [q / total * valid for q in probs]

    # --- capacity-slot assignment: the exact ``core.dispatch.plan``
    # non-priority semantics.  A positive assignment's slot is the count
    # of positive same-expert assignments strictly earlier in flat
    # token-major order (what the stable argsort there computes): a
    # running per-expert count, walked in that order on the scalar core.
    # Zero-weight assignments (masked/underflowed) take position ==
    # capacity.  Telemetry (``router.route_telemetry``): hard assignment
    # counts and capacity-truncation drops per expert.
    def zero(ei, carry):
        cnt_ref[ei] = 0
        load_ref[ei] = 0.0
        over_ref[ei] = 0.0
        return carry

    jax.lax.fori_loop(0, e, zero, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)

    def route(ti, carry):
        sel = row == ti
        for j in range(k):
            ej = jnp.sum(jnp.where(sel, idxs[j], 0.0)).astype(jnp.int32)
            wj = jnp.sum(jnp.where(sel, weights[j], 0.0))
            assigned = wj > 0.0
            c = cnt_ref[ej]
            p = jnp.where(assigned, c, capacity)
            kept = p < capacity
            cnt_ref[ej] = c + assigned.astype(jnp.int32)
            load_ref[ej] = load_ref[ej] + jnp.where(assigned, 1.0, 0.0)
            over_ref[ej] = over_ref[ej] + jnp.where(
                assigned & jnp.logical_not(kept), 1.0, 0.0)
            a = ti * k + j
            e_ref[a] = ej
            p_ref[a] = p
            w_ref[a] = jnp.where(kept, wj, 0.0)
        return carry

    jax.lax.fori_loop(0, t, route, 0)

    # --- scatter -> expert FFN -> weighted combine.
    _scatter_into(buf_ref, xs_ref, e_ref, p_ref, n=t * k, k=k,
                  capacity=capacity)
    _expert_ffn_into(out_ref, buf_ref, w1_ref, w2_ref, w3_ref,
                     n_experts=e, activation=activation, dt=x_ref.dtype)
    _combine_rows(y_ref, acc_ref, out_ref, e_ref, p_ref, w_ref, k=k,
                  capacity=capacity)


@functools.partial(jax.jit, static_argnames=("k", "capacity", "activation",
                                             "interpret", "vmem_limit"))
def _decode_step(x, valid, wg, w1, w2, w3, *, k, capacity, activation,
                 interpret, vmem_limit):
    t, d = x.shape
    e = wg.shape[-1]
    f = w1.shape[-1]
    gated = activation == "swiglu"
    valid2 = valid.astype(jnp.float32).reshape(t, 1)
    kernel = functools.partial(_decode_kernel, k=k, capacity=capacity,
                               activation=activation)
    in_specs = [
        pl.BlockSpec((t, d), lambda i: (0, 0)),                # x
        pl.BlockSpec((t, 1), lambda i: (0, 0)),                # valid
        pl.BlockSpec((d, e), lambda i: (0, 0)),                # wg
        pl.BlockSpec((e, d, f), lambda i: (0, 0, 0)),          # w1
        pl.BlockSpec((e, f, d), lambda i: (0, 0, 0)),          # w2
    ]
    operands = [x, valid2, wg, w1, w2]
    if gated:
        in_specs.append(pl.BlockSpec((e, d, f), lambda i: (0, 0, 0)))
        operands.append(w3)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    y, load, over = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((t, d), lambda i: (0, 0)), smem, smem),
        out_shape=(jax.ShapeDtypeStruct((t, d), x.dtype),
                   jax.ShapeDtypeStruct((e,), jnp.float32),
                   jax.ShapeDtypeStruct((e,), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32),         # x staged
                        pltpu.VMEM((e, capacity, d), jnp.float32),
                        pltpu.VMEM((e, capacity, d), jnp.float32),
                        pltpu.VMEM((t, d), jnp.float32),         # y acc
                        pltpu.SMEM((t * k,), jnp.int32),         # expert
                        pltpu.SMEM((t * k,), jnp.int32),         # slot
                        pltpu.SMEM((t * k,), jnp.float32),       # weight
                        pltpu.SMEM((e,), jnp.int32)],            # counts
        compiler_params=platform.compiler_params(vmem_limit),
        interpret=interpret,
    )(*operands)
    return y, load, over


def decode_step(x: jax.Array, valid: jax.Array, wg: jax.Array,
                w1: jax.Array, w2: jax.Array, w3: jax.Array | None = None,
                *, k: int, capacity: int, activation: str = "relu",
                interpret: bool | None = None,
                vmem_limit: int | None = None):
    """One fused MoE decode step (noisy_topk eval routing).

    x: [T, d] decode batch; valid: [T] f32 slot-occupancy mask; wg:
    [d, E] gate; w1/w2(/w3): [E, d, f]/[E, f, d]/([E, d, f]) expert
    weights.  Returns ``(y [T, d], expert_load [E] f32, overflow [E]
    f32)`` — the unfused route -> dispatch -> expert_ffn -> combine
    pipeline's output and telemetry in one launch.
    """
    e = wg.shape[-1]
    if k < 1 or k > e:
        raise ValueError(f"fused decode needs 1 <= k <= E: k={k}, E={e}")
    if activation == "swiglu" and w3 is None:
        raise ValueError("activation='swiglu' needs w3")
    return _decode_step(x, valid, wg, w1, w2, w3, k=k, capacity=capacity,
                        activation=activation,
                        interpret=platform.interpret_mode(interpret),
                        vmem_limit=vmem_limit)


# ---------------------------------------------------------------------------
# plan-mode fusion: dispatch -> grouped matmul(s) -> combine over explicit
# plans (expert_choice MoE, MoA routed projections)
# ---------------------------------------------------------------------------

def _routed_kernel(in_e_ref, in_p_ref, out_e_ref, out_p_ref, out_w_ref,
                   x_ref, *rest, k_in: int, k_out: int, capacity: int,
                   n_experts: int, mode: str, activation: str):
    if mode == "ffn":
        if activation == "swiglu":
            w1_ref, w2_ref, w3_ref, y_ref, *scratch = rest
        else:
            w1_ref, w2_ref, y_ref, *scratch = rest
            w3_ref = None
    else:
        w_ref, y_ref, *scratch = rest
    xs_ref, buf_ref, out_ref, acc_ref = scratch

    xs_ref[...] = x_ref[...].astype(jnp.float32)
    _scatter_into(buf_ref, xs_ref, in_e_ref, in_p_ref,
                  n=xs_ref.shape[0] * k_in, k=k_in, capacity=capacity)
    if mode == "ffn":
        _expert_ffn_into(out_ref, buf_ref, w1_ref, w2_ref, w3_ref,
                         n_experts=n_experts, activation=activation,
                         dt=x_ref.dtype)
    else:
        _proj_into(out_ref, buf_ref, w_ref, n_experts=n_experts,
                   dt=x_ref.dtype)
    _combine_rows(y_ref, acc_ref, out_ref, out_e_ref, out_p_ref, out_w_ref,
                  k=k_out, capacity=capacity)


@functools.partial(jax.jit, static_argnames=("n_experts", "capacity",
                                             "mode", "activation",
                                             "out_dtype", "interpret",
                                             "vmem_limit"))
def _routed_apply(x, in_eidx, in_pos, out_eidx, out_pos, out_w, w1, w2, w3,
                  *, n_experts, capacity, mode, activation, out_dtype,
                  interpret, vmem_limit):
    t_in, d_in = x.shape
    k_in = in_eidx.shape[1]
    k_out = out_eidx.shape[1]
    t_out = out_eidx.shape[0]
    e = n_experts
    if mode == "ffn":
        f = w1.shape[-1]
        d_out = w2.shape[-1]
    else:
        d_out = w1.shape[-1]
    kernel = functools.partial(_routed_kernel, k_in=k_in, k_out=k_out,
                               capacity=capacity, n_experts=e, mode=mode,
                               activation=activation)
    in_specs = [pl.BlockSpec((t_in, d_in), lambda i, *_: (0, 0))]
    operands = [x]
    if mode == "ffn":
        in_specs += [pl.BlockSpec((e, d_in, f), lambda i, *_: (0, 0, 0)),
                     pl.BlockSpec((e, f, d_out), lambda i, *_: (0, 0, 0))]
        operands += [w1, w2]
        if activation == "swiglu":
            in_specs.append(
                pl.BlockSpec((e, d_in, f), lambda i, *_: (0, 0, 0)))
            operands.append(w3)
    else:
        in_specs.append(
            pl.BlockSpec((e, d_in, d_out), lambda i, *_: (0, 0, 0)))
        operands.append(w1)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((t_out, d_out), lambda i, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((t_in, d_in), jnp.float32),
                            pltpu.VMEM((e, capacity, d_in), jnp.float32),
                            pltpu.VMEM((e, capacity, d_out), jnp.float32),
                            pltpu.VMEM((t_out, d_out), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t_out, d_out), out_dtype),
        compiler_params=platform.compiler_params(vmem_limit),
        interpret=interpret,
    )(in_eidx.reshape(-1), in_pos.reshape(-1), out_eidx.reshape(-1),
      out_pos.reshape(-1), out_w.astype(jnp.float32).reshape(-1),
      *operands)


def routed_apply(x: jax.Array, in_eidx: jax.Array, in_pos: jax.Array,
                 out_eidx: jax.Array, out_pos: jax.Array,
                 out_w: jax.Array, w1: jax.Array,
                 w2: jax.Array | None = None, w3: jax.Array | None = None,
                 *, n_experts: int, capacity: int, mode: str = "ffn",
                 activation: str = "relu", out_dtype=None,
                 interpret: bool | None = None,
                 vmem_limit: int | None = None) -> jax.Array:
    """Fused dispatch -> grouped matmul(s) -> combine over explicit plans.

    ``in_eidx``/``in_pos`` ([T_in, k_in]) scatter rows of ``x`` into the
    [E, C, d_in] buffer; ``mode="ffn"`` applies the two(/three)-matrix
    expert FFN, ``mode="proj"`` the single grouped projection ``w1``;
    ``out_eidx``/``out_pos``/``out_w`` ([T_out, k_out]) drive the
    weighted gather back to rows.  Token-major [T, k] and MoA's
    assignment-major [T·k, 1] plan views both work — k is just a shape.
    """
    if mode == "ffn" and activation == "swiglu" and w3 is None:
        raise ValueError("activation='swiglu' needs w3")
    return _routed_apply(x, in_eidx, in_pos, out_eidx, out_pos, out_w, w1,
                         w2, w3, n_experts=n_experts, capacity=capacity,
                         mode=mode, activation=activation,
                         out_dtype=out_dtype or x.dtype,
                         interpret=platform.interpret_mode(interpret),
                         vmem_limit=vmem_limit)
