"""Seeded weights, made on the device, in the benchmark's own naming.

Every weight is one named leaf (``embed``, ``wq``, ``w1``, ...), drawn
from a key folded from the run's seed and the leaf's name, so the same
seed gives the same values wherever they are made.  Layer leaves carry a
leading ``[layers]`` axis.  The program under test gets these values in
its own parameter tree (``program_params``); the plain reference gets
them under the names here (``make``).  Both draw them in one jitted call.

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


def shapes(m: dict) -> dict:
    """name -> (shape, dtype, fan_in or None for ones/unit normal)."""
    d, L, E, V = m["d_model"], m["n_layers"], m["n_experts"], m["vocab"]
    H, KV, hd, f = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_expert"]
    out = {
        "embed": ((V, d), BF16, None),
        "unembed": ((d, V), BF16, d),
        "ln_f": ((d,), F32, "ones"),
        "ln1": ((L, d), F32, "ones"),
        "ln2": ((L, d), F32, "ones"),
        "wq": ((L, d, H, hd), BF16, d),
        "wk": ((L, d, KV, hd), BF16, d),
        "wv": ((L, d, KV, hd), BF16, d),
        "wo": ((L, H, hd, d), BF16, H * hd),
        "wg": ((L, d, E), F32, d),
        "w1": ((L, E, d, f), BF16, d),
        "w3": ((L, E, d, f), BF16, d),
        "w2": ((L, E, f, d), BF16, f),
    }
    if m.get("d_dense"):
        F = m["d_dense"]
        out.update({"mlp_w1": ((L, d, F), BF16, d),
                    "mlp_w3": ((L, d, F), BF16, d),
                    "mlp_w2": ((L, F, d), BF16, F)})
    return out


def _leaf(key, name, shape, dtype, fan_in):
    if fan_in == "ones":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    x = jax.random.normal(k, shape, F32)
    if fan_in is not None:
        x = x / math.sqrt(fan_in)
    return x.astype(dtype)


def make(m: dict, seed: int) -> dict:
    """All leaves by name, in one jitted call on the default device."""
    spec = shapes(m)

    def build(key):
        return {n: _leaf(key, n, s, dt, fi) for n, (s, dt, fi) in spec.items()}
    return jax.jit(build)(base_key(seed))


# The program's parameter paths (``repro.models.lm.lm_defs``) for each
# leaf; layer leaves sit under the stacked scan group.
_LAYER = "blocks/periods/pos0/"
PROGRAM_PATHS = {
    "embed/table": "embed", "unembed/w": "unembed", "ln_f/scale": "ln_f",
    _LAYER + "ln1/scale": "ln1", _LAYER + "ln2/scale": "ln2",
    _LAYER + "attn/wq": "wq", _LAYER + "attn/wk": "wk",
    _LAYER + "attn/wv": "wv", _LAYER + "attn/wo": "wo",
    _LAYER + "moe/gate/wg": "wg", _LAYER + "moe/w1": "w1",
    _LAYER + "moe/w3": "w3", _LAYER + "moe/w2": "w2",
    _LAYER + "mlp/w1": "mlp_w1", _LAYER + "mlp/w3": "mlp_w3",
    _LAYER + "mlp/w2": "mlp_w2",
}


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def program_params(m: dict, defs, seed: int):
    """The program's parameter tree (``defs`` from ``lm.lm_defs``) filled
    with this module's leaves, made in one jitted call.  A program leaf
    with no counterpart here, or of another shape or dtype, is an error."""
    spec = shapes(m)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "axes"))
    names = []
    for path, d in flat:
        name = PROGRAM_PATHS.get(_path(path))
        if name is None or name not in spec:
            raise KeyError(f"program parameter {_path(path)} has no "
                           "benchmark weight")
        shape, dtype, _ = spec[name]
        if tuple(d.shape) != shape or jnp.dtype(d.dtype) != jnp.dtype(dtype):
            raise ValueError(f"{_path(path)}: program {d.shape} {d.dtype} "
                             f"vs benchmark {shape} {jnp.dtype(dtype)}")
        names.append(name)

    def build(key):
        return jax.tree_util.tree_unflatten(
            tree, [_leaf(key, n, *spec[n]) for n in names])
    return jax.jit(build)(base_key(seed))


def leaf_names(defs) -> list[str]:
    """Benchmark names of the program's leaves, in the program's order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "axes"))
    return [PROGRAM_PATHS[_path(p)] for p, _ in flat]
