"""Seeded weights, made on the device, in the benchmark's own naming.

Every weight is one named leaf (``embed``, ``w1``, ...), drawn from a
key folded from the run's seed and the leaf's name, so the same seed
gives the same values wherever they are made.  The configuration's
architecture module (``bench/archs/<arch>.py``) names the leaves and
their shapes (``shapes``) and where each sits in the program's tree
(``PROGRAM_PATHS``).  The program under test gets these values in its
own parameter tree (``program_params``); the plain reference gets them
by name (``make``).  Both draw them in one jitted call.

Scales keep every activation of the random-weight model O(1): unit
normal embeddings, projections N(0, 1/fan_in), unit norm scales, and the
router's gate drawn N(0, 1/d) so that routing spreads over all experts
(a zero gate, the model's own start of training, would tie every score).
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16
F32 = jnp.float32


def base_key(seed: int) -> jax.Array:
    """A key for any whole-number seed (the low 32 bits and the rest)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, name, shape, dtype, fan_in):
    if fan_in == "ones":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    x = jax.random.normal(k, shape, F32)
    if fan_in is not None:
        x = x / math.sqrt(fan_in)
    return x.astype(dtype)


def make(arch, m: dict, seed: int) -> dict:
    """All leaves of the architecture module ``arch`` by name, in one
    jitted call on the default device."""
    spec = arch.shapes(m)

    def build(key):
        return {n: _leaf(key, n, s, dt, fi) for n, (s, dt, fi) in spec.items()}
    return jax.jit(build)(base_key(seed))


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def _flat(defs):
    return jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "axes"))


def leaf_names(arch, m: dict, defs) -> list[str]:
    """Benchmark names of the program's leaves (``defs`` from
    ``lm.lm_defs``), in the program's order.  A program leaf with no
    counterpart in ``arch.shapes(m)``, or of another shape or dtype, is
    an error."""
    spec = arch.shapes(m)
    names = []
    for path, d in _flat(defs)[0]:
        name = arch.PROGRAM_PATHS.get(_path(path))
        if name is None or name not in spec:
            raise KeyError(f"program parameter {_path(path)} has no "
                           "benchmark weight")
        shape, dtype, _ = spec[name]
        if tuple(d.shape) != shape or jnp.dtype(d.dtype) != jnp.dtype(dtype):
            raise ValueError(f"{_path(path)}: program {d.shape} {d.dtype} "
                             f"vs benchmark {shape} {jnp.dtype(dtype)}")
        names.append(name)
    return names


def program_params(arch, m: dict, defs, seed: int):
    """The program's parameter tree filled with the leaves of ``make``,
    made in one jitted call."""
    spec = arch.shapes(m)
    names = leaf_names(arch, m, defs)
    tree = _flat(defs)[1]

    def build(key):
        return jax.tree_util.tree_unflatten(
            tree, [_leaf(key, n, *spec[n]) for n in names])
    return jax.jit(build)(base_key(seed))
