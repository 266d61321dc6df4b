"""Grouped expert matmul (megablox-style GMM) as a Pallas TPU kernel.

The expert FFN over capacity-dispatched buffers — einsum('ecd,edf->ecf') —
is the paper's compute hot-spot (§3.2: the experts carry ~40% of total
FLOPs in the paper's models, and "we can increase computational efficiency
simply by using a larger hidden layer").  On GPU the reference batches
per-expert GEMMs; the TPU-native shape is one kernel whose grid walks
(expert, row-block, col-block, k-block) with an f32 VMEM accumulator,
MXU-aligned 128x128 tiles, and the activation fused into the final k-step
epilogue so the [E, C, d_ff] hidden never round-trips HBM at f32.

Grid iteration order is (e, m, n, k) with k innermost: the accumulator tile
stays VMEM-resident across the k loop (revolving output), and the x
row-block is reused across all n — the standard TPU blocked-matmul
schedule.  VMEM working set per step (bm=bn=bk=128): x tile 32 KiB +
w tile 32 KiB + f32 acc 64 KiB ~= 128 KiB, far under the ~16 MiB budget;
larger bn/bk amortize grid overhead until the d_ff dimension is consumed.

Non-tile-aligned shapes are zero-padded up to the block plan (see
:func:`plan_blocks`) and the output trimmed — zero rows/columns are inert
through the matmul and the fused activations (relu(0) == silu(0) == 0), so
padding never changes the visible result.

Training: :func:`gmm` carries a ``jax.custom_vjp`` so the Pallas path is
differentiable end-to-end.  Both cotangents are themselves grouped matmuls
and reuse the same kernel —

    dx = gmm(dyʹ, wᵀ)          [E,C,N] x [E,N,K] -> [E,C,K]
    dw = gmm(xᵀ, dyʹ)          [E,K,C] x [E,C,N] -> [E,K,N]

where dyʹ folds the activation derivative in: the pre-activation z is
rematerialized with one extra no-activation GMM (the Appendix-D
"recompute expert activations on the backward pass" policy) rather than
saved, keeping forward residuals at (x, w).

Tile sizes come from a **measured tuning table** when the caller leaves
them unset: ``plan_blocks`` consults ``gmm_tunings.json`` (seeded by
``make tune-kernels``, exact (E, C, K, N, dtype) keys) before its static
128 defaults — on this interpret-mode host per-grid-step overhead
dominates, so fewer/bigger blocks win by integer factors (the
``kernel_backend_gmm_pallas`` gap in BENCH_micro.json).  Explicit
``bm/bn/bk`` arguments always override the table.

``interpret`` resolves at call time through ``platform.interpret_mode``:
compiled on a TPU, the Pallas interpreter elsewhere.
"""
from __future__ import annotations

import functools
import json
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import platform


def round_up(x: int, m: int) -> int:
    """Smallest multiple of m >= x (shared by the kernel modules)."""
    return -(-x // m) * m


def _sublane(dtype) -> int:
    """Minimum TPU sublane tile for a dtype (second-to-last dim)."""
    return 16 if dtype == jnp.bfloat16 else 8


# --- measured tiling table (docs/kernels.md §Tiling autotune) --------------

# Static fallback tile edge when a shape has no measured entry.
DEFAULT_TILE = 128

# Env var overriding the committed table path (tests point it at tmp
# files; an empty value falls through to the default).
TUNINGS_ENV = "REPRO_GMM_TUNINGS"
_DEFAULT_TUNINGS_PATH = os.path.join(os.path.dirname(__file__),
                                     "gmm_tunings.json")

_tunings_cache: tuple[str, dict] | None = None


def tunings_path() -> str:
    return os.environ.get(TUNINGS_ENV) or _DEFAULT_TUNINGS_PATH


def tuning_key(e: int, c: int, k: int, n: int, dtype) -> str:
    """Exact-shape table key: ``{E}x{C}x{K}x{N}x{dtype}``."""
    return f"{e}x{c}x{k}x{n}x{jnp.dtype(dtype).name}"


def load_tunings(path: str | None = None) -> dict:
    """Load the measured shape -> (bm, bn, bk) table (missing file -> {}).

    Keys beginning with ``_`` are metadata (tuner provenance) and are
    skipped.  Cached per path; call :func:`invalidate_tunings` after
    re-tuning or pointing ``REPRO_GMM_TUNINGS`` elsewhere mid-process.

    When ``REPRO_GMM_TUNINGS`` supplies the path, the override is
    *validated*: a missing or unparseable file raises
    ``KernelBackendError`` instead of silently falling back to the static
    defaults (an empty value keeps the documented "unset" meaning — the
    committed table).
    """
    global _tunings_cache
    env_override = path is None and bool(os.environ.get(TUNINGS_ENV))
    path = path or tunings_path()
    if _tunings_cache is not None and _tunings_cache[0] == path:
        return _tunings_cache[1]
    table: dict = {}
    try:
        with open(path) as f:
            raw = json.load(f)
        table = {key: tuple(int(v) for v in val)
                 for key, val in raw.items() if not key.startswith("_")}
    except FileNotFoundError:
        if env_override:
            from repro.kernels.backend import KernelBackendError
            raise KernelBackendError(
                f"{TUNINGS_ENV}={path!r} points at a missing GMM tunings "
                "file — fix the path or unset the variable (an empty "
                "value means 'use the committed table')") from None
    except (json.JSONDecodeError, ValueError, TypeError) as err:
        if env_override:
            from repro.kernels.backend import KernelBackendError
            raise KernelBackendError(
                f"{TUNINGS_ENV}={path!r} is not a valid GMM tunings "
                f"table: {err}") from err
        raise
    _tunings_cache = (path, table)
    return table


def invalidate_tunings() -> None:
    """Drop the cached table (next lookup re-reads the file).

    Note: jitted callers that already traced with ``bm=bn=bk=None``
    resolved the table at trace time; the jit cache must also be cleared
    (or explicit tiles passed) for a changed table to take effect.
    """
    global _tunings_cache
    _tunings_cache = None


def lookup_tiling(e: int, c: int, k: int, n: int,
                  dtype) -> tuple[int, int, int] | None:
    """Measured (bm, bn, bk) for an exact shape, or None (use defaults)."""
    return load_tunings().get(tuning_key(e, c, k, n, dtype))


class BlockPlan(NamedTuple):
    """A per-shard block spec for one grouped matmul: padded operand shapes
    plus the (bm, bn, bk) tile walk.  ``padded == shape`` iff the local
    dims were already tile-aligned."""
    e: int
    c: int          # padded row dim (capacity)
    k: int          # padded contraction dim
    n: int          # padded output dim
    bm: int
    bn: int
    bk: int

    @property
    def grid(self) -> tuple[int, int, int, int]:
        return (self.e, self.c // self.bm, self.n // self.bn,
                self.k // self.bk)


def plan_blocks(e: int, c: int, k: int, n: int, dtype=jnp.float32, *,
                bm: int | None = None, bn: int | None = None,
                bk: int | None = None) -> BlockPlan:
    """Derive the block plan for a (possibly non-tile-aligned) local shape.

    Tile sizes left as ``None`` consult the measured tuning table first
    (:func:`lookup_tiling`, exact-shape keys) and fall back to
    ``DEFAULT_TILE``; explicit values always win.  Blocks are clamped to
    the (tile-rounded) dims so small problems don't pad all the way to
    128, and dims are padded up to a whole number of blocks instead of
    asserting divisibility.
    """
    if bm is None and bn is None and bk is None:
        tuned = lookup_tiling(e, c, k, n, dtype)
        if tuned is not None:
            bm, bn, bk = tuned
    bm = DEFAULT_TILE if bm is None else bm
    bn = DEFAULT_TILE if bn is None else bn
    bk = DEFAULT_TILE if bk is None else bk
    sub = _sublane(dtype)
    bm = min(bm, round_up(c, sub))
    bn = min(bn, round_up(n, 128))
    bk = min(bk, round_up(k, 128))
    return BlockPlan(e=e, c=round_up(c, bm), k=round_up(k, bk),
                     n=round_up(n, bn), bm=bm, bn=bn, bk=bk)


def _pad3(x: jax.Array, d1: int, d2: int) -> jax.Array:
    """Zero-pad the trailing two dims of [E, a, b] up to (d1, d2)."""
    e, a, b = x.shape
    if a == d1 and b == d2:
        return x
    return jnp.pad(x, ((0, 0), (0, d1 - a), (0, d2 - b)))


def _act(out: jax.Array, activation: str) -> jax.Array:
    if activation == "relu":
        return jnp.maximum(out, 0.0)
    if activation == "silu":
        return out * (1.0 / (1.0 + jnp.exp(-out)))
    if activation != "none":
        # Real exception, not an assert: under `python -O` an assert is
        # stripped and an unknown activation would silently run identity.
        raise ValueError(f"unknown gmm activation: {activation!r} "
                         f"(expected 'none', 'relu', or 'silu')")
    return out


def _act_grad(z: jax.Array, activation: str) -> jax.Array:
    """d act(z) / dz at f32."""
    if activation == "relu":
        return (z > 0.0).astype(jnp.float32)
    if activation == "silu":
        s = jax.nn.sigmoid(z)
        return s * (1.0 + z * (1.0 - s))
    if activation != "none":
        # Same `python -O` hazard as _act: stripped assert -> grad of 1s.
        raise ValueError(f"unknown gmm activation: {activation!r} "
                         f"(expected 'none', 'relu', or 'silu')")
    return jnp.ones_like(z)


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int, activation: str):
    @pl.when(pl.program_id(3) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == n_k - 1)
    def _epilogue():
        o_ref[0] = _act(acc_ref[...], activation).astype(o_ref.dtype)


def _gmm_raw(x: jax.Array, w: jax.Array, activation: str,
             bm: int, bn: int, bk: int, interpret: bool) -> jax.Array:
    """Pad -> pallas_call -> trim.  No autodiff rule (see ``gmm``)."""
    e, c, k = x.shape
    _, _, n = w.shape
    bp = plan_blocks(e, c, k, n, x.dtype, bm=bm, bn=bn, bk=bk)
    xp = _pad3(x, bp.c, bp.k)
    wp = _pad3(w, bp.k, bp.n)
    n_k = bp.k // bp.bk
    kernel = functools.partial(_gmm_kernel, n_k=n_k, activation=activation)
    out = pl.pallas_call(
        kernel,
        grid=bp.grid,
        in_specs=[
            pl.BlockSpec((1, bp.bm, bp.bk), lambda e, m, n_, k_: (e, m, k_)),
            pl.BlockSpec((1, bp.bk, bp.bn), lambda e, m, n_, k_: (e, k_, n_)),
        ],
        out_specs=pl.BlockSpec((1, bp.bm, bp.bn),
                               lambda e, m, n_, k_: (e, m, n_)),
        out_shape=jax.ShapeDtypeStruct((e, bp.c, bp.n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bp.bm, bp.bn), jnp.float32)],
        compiler_params=platform.compiler_params(),
        interpret=interpret,
    )(xp, wp)
    if (bp.c, bp.n) != (c, n):
        out = out[:, :c, :n]
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _gmm(x, w, activation, bm, bn, bk, interpret):
    return _gmm_raw(x, w, activation, bm, bn, bk, interpret)


def _gmm_fwd(x, w, activation, bm, bn, bk, interpret):
    return _gmm_raw(x, w, activation, bm, bn, bk, interpret), (x, w)


def _gmm_bwd(activation, bm, bn, bk, interpret, res, g):
    x, w = res
    if activation != "none":
        # Rematerialize the pre-activation z (one extra GMM) and fold the
        # activation derivative into the incoming cotangent.
        z = _gmm_raw(x, w, "none", bm, bn, bk, interpret)
        g = (g.astype(jnp.float32)
             * _act_grad(z.astype(jnp.float32), activation)).astype(g.dtype)
    dx = _gmm_raw(g, jnp.swapaxes(w, 1, 2), "none", bm, bn, bk, interpret)
    dw = _gmm_raw(jnp.swapaxes(x, 1, 2), g, "none", bm, bn, bk, interpret)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


@functools.partial(jax.jit, static_argnames=("activation", "bm", "bn", "bk",
                                             "interpret"))
def gmm(x: jax.Array, w: jax.Array, *, activation: str = "none",
        bm: int | None = None, bn: int | None = None, bk: int | None = None,
        interpret: bool | None = None) -> jax.Array:
    """[E, C, K] x [E, K, N] -> [E, C, N] with optional fused activation.

    Differentiable (custom VJP); non-tile-aligned C/K/N are zero-padded to
    the :func:`plan_blocks` boundaries and the output trimmed.  Tile sizes
    left as ``None`` use the measured tuning table / static defaults via
    :func:`plan_blocks` — each backward-pass GMM re-plans for its own
    operand shapes, so grad matmuls get their own tuned tiles.
    """
    return _gmm(x, w, activation, bm, bn, bk,
                platform.interpret_mode(interpret))
