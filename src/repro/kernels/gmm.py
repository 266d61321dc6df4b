"""Grouped expert matmul (megablox-style GMM) as a Pallas TPU kernel.

The expert FFN over capacity-dispatched buffers — einsum('ecd,edf->ecf') —
is the paper's compute hot-spot (§3.2: the experts carry ~40% of total
FLOPs in the paper's models, and "we can increase computational efficiency
simply by using a larger hidden layer").  On GPU the reference batches
per-expert GEMMs; the TPU-native shape is one kernel whose grid walks
(expert, row-block, col-block, k-block) with an f32 VMEM accumulator,
MXU-aligned tiles, and the activation fused into the final k-step
epilogue so the [E, C, d_ff] hidden never round-trips HBM at f32.

Grid iteration order is (e, m, n, k) with k innermost: the accumulator tile
stays VMEM-resident across the k loop (revolving output), and the x
row-block is reused across all n — the standard TPU blocked-matmul
schedule.  Each grid step costs ~0.35 us of fixed overhead on a v5e
whatever its work, so a static 128^3 walk leaves a large GMM
grid-step-bound: at 8 x 8192 x 7168 x 4864 bf16 it is 1.09M steps.

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

Tile sizes, when the caller leaves them unset, come from a **tile rule**
(:func:`rule_tiles`): among tiles whose edges divide the dims (bn, bk:
multiples of 128; bm: of the dtype's sublane) or are power-of-two
multiples of those units, keep the one of least modelled time —
max(padded FLOPs / MXU peak, HBM bytes with x re-read per n-block and w
per m-block / HBM bandwidth) + ~0.35 us a grid step + the copies any
padding forces — whose VMEM footprint (:func:`vmem_bytes`) fits the
budget handed to Mosaic.  It is a pure function of (E, C, K, N, dtype,
budget), so every caller gets it: serve prefill and decode, each training
forward and backward matmul, MoA's projections, the per-shard shapes of
the expert-parallel path.  Precedence in :func:`plan_blocks`: explicit
``bm/bn/bk``; ``autotune=False`` (``MoEArgs.gmm_autotune``) pins
``DEFAULT_TILE``; an exact-shape entry of ``gmm_tunings.json`` (an
override; its committed entries are float32 shapes tuned in interpret mode
on a CPU, which no chip path reaches); else the rule.
:func:`plan_sources` counts, at trace time, how each plan was resolved.

``interpret`` resolves at call time through ``platform.interpret_mode``:
compiled on a TPU, the Pallas interpreter elsewhere.
"""
from __future__ import annotations

import collections
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


# --- tiling table (docs/kernels.md §Tiling autotune) ------------------------

# The tile edge pinned by ``autotune=False`` and given to any explicit tile
# argument left unset.
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
    ``KernelBackendError`` instead of silently falling back to the tile
    rule (an empty value keeps the documented "unset" meaning — the
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
    """Table (bm, bn, bk) for an exact shape, or None (use the rule)."""
    return load_tunings().get(tuning_key(e, c, k, n, dtype))


class BlockPlan(NamedTuple):
    """A per-shard block spec for one grouped matmul: padded operand shapes
    plus the (bm, bn, bk) tile walk.  ``padded == shape`` iff the local
    dims were already tile-aligned.  ``source`` says how the tiles were
    resolved: ``explicit``, ``table``, ``rule`` or ``pinned``."""
    e: int
    c: int          # padded row dim (capacity)
    k: int          # padded contraction dim
    n: int          # padded output dim
    bm: int
    bn: int
    bk: int
    source: str

    @property
    def grid(self) -> tuple[int, int, int, int]:
        return (self.e, self.c // self.bm, self.n // self.bn,
                self.k // self.bk)


# --- the tile rule: tiles sized from the shape and the VMEM budget ---------

# Fixed cost of one grid step on the chip the kernels target: ~0.35 us,
# measured on a v5e (a 128^3 walk runs at 0.27-0.37 us a step whatever the
# work in it).  The MXU peak and HBM bandwidth are the chip's, from
# ``platform``.
_STEP_S = 0.35e-6


def vmem_bytes(bm: int, bn: int, bk: int, dtype) -> int:
    """Scoped VMEM one grid step holds: double-buffered x, w and out tiles,
    the f32 accumulator, and what Mosaic adds in the kernel body — a copy
    of the x tile fed to the MXU, an f32 tile for the epilogue's
    activation, and under one f32 vreg row (128 lanes) a tile row of
    scratch.  Checked against the least scoped-VMEM limit the v5e
    compiler accepts for bf16 tiles from 256^3 to 1024^3: never under
    it, and within 0.5 MiB of it where the epilogue is an activation."""
    size = jnp.dtype(dtype).itemsize
    return (2 * (bm * bk + bk * bn + bm * bn) + bm * bk) * size \
        + (2 * bn + 128) * bm * 4


def tile_edges(dim: int, unit: int) -> list[int]:
    """Candidate tile edges for one dim: the multiples of ``unit`` that
    divide the dim rounded up to ``unit`` (no padding past that rounding),
    and the power-of-two multiples of ``unit`` below it (these pad further;
    the cost model charges their copy)."""
    full = round_up(dim, unit)
    q = full // unit
    divisors = {unit * d for d in range(1, q + 1) if q % d == 0}
    powers = {unit << j for j in range(q.bit_length()) if unit << j <= full}
    return sorted(divisors | powers)


def plan_seconds(e: int, c: int, k: int, n: int, bm: int, bn: int, bk: int,
                 dtype) -> float:
    """Modelled time of one GMM call walking (bm, bn, bk) tiles: the larger
    of the padded FLOPs at peak and the HBM bytes at peak bandwidth (x read
    once per n-block, w once per m-block, out written once), plus the
    per-step cost, plus the copies that padding and trimming make."""
    size = jnp.dtype(dtype).itemsize
    cp, kp, np_ = round_up(c, bm), round_up(k, bk), round_up(n, bn)
    m_blocks, n_blocks = cp // bm, np_ // bn
    steps = e * m_blocks * n_blocks * (kp // bk)
    flops = 2 * e * cp * kp * np_
    moved = e * size * (cp * kp * n_blocks + kp * np_ * m_blocks + cp * np_)
    copied = 0
    for (a, b), (pa, pb) in (((c, k), (cp, kp)), ((k, n), (kp, np_)),
                             ((c, n), (cp, np_))):
        if (a, b) != (pa, pb):
            copied += e * size * (a * b + pa * pb)
    return (max(flops / platform.PEAK_BF16_FLOPS,
                moved / platform.HBM_BYTES_PER_S)
            + _STEP_S * steps + copied / platform.HBM_BYTES_PER_S)


@functools.lru_cache(maxsize=1024)
def rule_tiles(e: int, c: int, k: int, n: int, dtype_name: str,
               vmem_limit: int) -> tuple[int, int, int]:
    """The (bm, bn, bk) of least :func:`plan_seconds` whose
    :func:`vmem_bytes` fits ``vmem_limit``; ties go to fewer grid steps,
    then to the smaller tiles.  Pure in its arguments."""
    dtype = jnp.dtype(dtype_name)
    best = None
    for bm in tile_edges(c, _sublane(dtype)):
        for bn in tile_edges(n, 128):
            for bk in tile_edges(k, 128):
                if vmem_bytes(bm, bn, bk, dtype) > vmem_limit:
                    continue
                steps = (-(-c // bm)) * (-(-n // bn)) * (-(-k // bk))
                key = (plan_seconds(e, c, k, n, bm, bn, bk, dtype), steps,
                       bm, bn, bk)
                if best is None or key < best:
                    best = key
    if best is None:
        raise ValueError(f"no GMM tile of {e}x{c}x{k}x{n}x{dtype_name} "
                         f"fits {vmem_limit} bytes of VMEM")
    return best[2:]


# How each GMM plan traced in this process was resolved.
_PLAN_SOURCES: collections.Counter = collections.Counter()


def plan_sources() -> dict[str, int]:
    """GMM plans traced in this process so far, by how their tiles were
    resolved (``explicit`` / ``table`` / ``rule`` / ``pinned``).  Counted
    once per traced kernel call, as backend.fallbacks() is."""
    return dict(_PLAN_SOURCES)


def plan_blocks(e: int, c: int, k: int, n: int, dtype=jnp.float32, *,
                bm: int | None = None, bn: int | None = None,
                bk: int | None = None, autotune: bool = True) -> BlockPlan:
    """Derive the block plan for a (possibly non-tile-aligned) local shape.

    Precedence: explicit ``bm/bn/bk`` (any left ``None`` take
    ``DEFAULT_TILE``); else, with ``autotune`` off, ``DEFAULT_TILE`` for
    all three ("pinned"); else the tuning table's exact-shape entry
    (:func:`lookup_tiling`); else the rule (:func:`rule_tiles`), sized
    against ``platform.DEFAULT_VMEM_LIMIT``, the limit the kernel hands
    Mosaic.  Blocks are clamped to the (tile-rounded) dims so small
    problems don't pad all the way to 128, and dims are padded up to a
    whole number of blocks instead of asserting divisibility.
    """
    if bm is not None or bn is not None or bk is not None:
        source = "explicit"
    elif not autotune:
        source = "pinned"
    else:
        tuned = lookup_tiling(e, c, k, n, dtype)
        if tuned is not None:
            source = "table"
            bm, bn, bk = tuned
        else:
            source = "rule"
            bm, bn, bk = rule_tiles(e, c, k, n, jnp.dtype(dtype).name,
                                    platform.DEFAULT_VMEM_LIMIT)
    bm = DEFAULT_TILE if bm is None else bm
    bn = DEFAULT_TILE if bn is None else bn
    bk = DEFAULT_TILE if bk is None else bk
    sub = _sublane(dtype)
    bm = min(bm, round_up(c, sub))
    bn = min(bn, round_up(n, 128))
    bk = min(bk, round_up(k, 128))
    return BlockPlan(e=e, c=round_up(c, bm), k=round_up(k, bk),
                     n=round_up(n, bn), bm=bm, bn=bn, bk=bk, source=source)


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
             bm: int | None, bn: int | None, bk: int | None, autotune: bool,
             interpret: bool) -> jax.Array:
    """Pad -> pallas_call -> trim.  No autodiff rule (see ``gmm``)."""
    e, c, k = x.shape
    _, _, n = w.shape
    bp = plan_blocks(e, c, k, n, x.dtype, bm=bm, bn=bn, bk=bk,
                     autotune=autotune)
    _PLAN_SOURCES[bp.source] += 1
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _gmm(x, w, activation, bm, bn, bk, autotune, interpret):
    return _gmm_raw(x, w, activation, bm, bn, bk, autotune, interpret)


def _gmm_fwd(x, w, activation, bm, bn, bk, autotune, interpret):
    return (_gmm_raw(x, w, activation, bm, bn, bk, autotune, interpret),
            (x, w))


def _gmm_bwd(activation, bm, bn, bk, autotune, interpret, res, g):
    x, w = res
    tiles = (bm, bn, bk, autotune, interpret)
    if activation != "none":
        # Rematerialize the pre-activation z (one extra GMM) and fold the
        # activation derivative into the incoming cotangent.
        z = _gmm_raw(x, w, "none", *tiles)
        g = (g.astype(jnp.float32)
             * _act_grad(z.astype(jnp.float32), activation)).astype(g.dtype)
    dx = _gmm_raw(g, jnp.swapaxes(w, 1, 2), "none", *tiles)
    dw = _gmm_raw(jnp.swapaxes(x, 1, 2), g, "none", *tiles)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


@functools.partial(jax.jit, static_argnames=("activation", "bm", "bn", "bk",
                                             "autotune", "interpret"))
def gmm(x: jax.Array, w: jax.Array, *, activation: str = "none",
        bm: int | None = None, bn: int | None = None, bk: int | None = None,
        autotune: bool = True, interpret: bool | None = None) -> jax.Array:
    """[E, C, K] x [E, K, N] -> [E, C, N] with optional fused activation.

    Differentiable (custom VJP); non-tile-aligned C/K/N are zero-padded to
    the :func:`plan_blocks` boundaries and the output trimmed.  Tile sizes
    left as ``None`` come from the tuning table, else from the tile rule
    (``autotune=False``: ``DEFAULT_TILE``) via :func:`plan_blocks` — each
    backward-pass GMM re-plans for its own operand shapes.
    """
    return _gmm(x, w, activation, bm, bn, bk, autotune,
                platform.interpret_mode(interpret))
