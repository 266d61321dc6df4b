"""Grouped-query attention and a softmax top-k MoE, every layer alike.

One decoder layer, as the configuration files that name ``gqa_moe``
state it:

    h  = rmsnorm(x) ;  x = x + GQA-attention(h)           (RoPE, causal)
    h  = rmsnorm(x) ;  x = x + MoE(h) [+ dense SwiGLU FFN(h)]

The MoE is ``reference.moe`` (softmax over the top k, first C
assignments an expert kept in batch order); the dense FFN, present where
``d_dense`` is not 0 (arctic's residual MLP), ``reference.mlp``.  Every
layer routes tokens.

An architecture module gives the benchmark, by name, what it needs of
one layer kind: the weights (``shapes``, ``PROGRAM_PATHS``), the
program's config at the configuration's sizes (``program_config``), the
reference's float32 layer (``layer``) and the counts (``token_flops``,
``moe_layers``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import flops
from bench import reference as ref
from bench.weights import BF16, F32

Q_BLOCK = 512


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


def program_config(conf: dict, m: dict, rehearsal: bool = False):
    """The program's ModelConfig at the sizes ``m``: its registry entry
    (``program_arch``) with those sizes, the Pallas kernels, and the
    router the file states (deterministic top-k at the stated capacity
    factor)."""
    from repro.configs.base import get_config
    from repro.core.router import RouterSpec

    cfg = get_config(conf["program_arch"]).replace(
        n_layers=m["n_layers"], d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"],
        n_experts=m["n_experts"], moe_k=m["k"], moe_d_ff=m["d_expert"],
        d_ff=m["d_dense"], vocab_size=m["vocab"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["eps"]),
        kernel_backend="pallas",
        router=RouterSpec(capacity_factor=float(m["capacity_factor"]),
                          noise=False))
    if rehearsal:
        cfg = cfg.replace(q_block=16, kv_block=16)
    if bool(cfg.dense_residual) != bool(m["d_dense"]):
        raise ValueError(f"{conf['name']}: dense residual FFN in the program "
                         f"({cfg.dense_residual}) and d_dense={m['d_dense']} "
                         "disagree")
    return cfg


_LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "wg", "w1", "w3", "w2",
               "mlp_w1", "mlp_w3", "mlp_w2")
# Contracted axis of each attention matrix (for the per-channel fp8
# control); the shared MoE and FFN state their own.
_CONTRACT = {"wq": 0, "wk": 0, "wv": 0, "wo": (0, 1)}


def _use(w, name, control):
    return ref._use(w, name, control, _CONTRACT[name])


def attention(w, h, m, control=None):
    """Causal GQA over one sequence. h: [S, d] -> [S, d]."""
    s = h.shape[0]
    pos = jnp.arange(s)
    h = ref._act(h, control)
    q = ref.rope(ref._mm("sd,dhk->shk", h, _use(w, "wq", control)), pos,
                 m["rope_theta"])
    k = ref.rope(ref._mm("sd,dhk->shk", h, _use(w, "wk", control)), pos,
                 m["rope_theta"])
    v = ref._mm("sd,dhk->shk", h, _use(w, "wv", control))
    kv, hd = k.shape[1], k.shape[2]
    g = q.shape[1] // kv
    qb = min(Q_BLOCK, s)
    n = -(-s // qb)
    q = jnp.pad(q, ((0, n * qb - s), (0, 0), (0, 0)))
    qblocks = q.reshape(n, qb, kv, g, hd)

    @jax.checkpoint
    def block(args):
        qi, i = args
        sc = jnp.einsum("qkgh,skh->kgqs", qi, k,
                        precision=ref.HI) / math.sqrt(hd)
        pq = i * qb + jnp.arange(qb)
        sc = jnp.where(pos[None, :] <= pq[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqs,skh->qkgh", p, v, precision=ref.HI)

    o = jax.lax.map(block, (qblocks, jnp.arange(n)))
    o = o.reshape(n * qb, kv * g, hd)[:s]
    return ref._mm("shk,hkd->sd", ref._act(o, control, (1, 2)),
                   _use(w, "wo", control))


def layer(params, i, x, m, control=None):
    """Layer i. x: [B, S, d] -> (x, aux, kept).  The MoE routes all B*S
    tokens of the call together, as one batch."""
    w = {n: params[n][i] for n in _LAYER_KEYS if n in params}
    b, s, d = x.shape
    h = ref.rmsnorm(x, w["ln1"], m["eps"])
    x = x + jax.lax.map(lambda hi: attention(w, hi, m, control), h)
    h = ref.rmsnorm(x, w["ln2"], m["eps"]).reshape(b * s, d)
    y, aux, kept = ref.moe(w, h, m, control)
    if m["d_dense"]:
        y = y + ref.mlp(w, h, control)
    return x + y.reshape(b, s, d), aux, kept


def token_flops(m: dict, context: float) -> float:
    """Forward FLOPs of one token that attends ``context`` positions (2
    per multiply-add): the attention projections, the scores and values,
    the router, the k experts, the dense FFN and the unembedding."""
    d, hd = m["d_model"], m["head_dim"]
    h, kv = m["n_heads"], m["n_kv_heads"]
    per_layer = (2 * d * hd * (2 * h + 2 * kv)       # q, k, v, o
                 + 2 * 2 * h * hd * context           # scores and values
                 + 2 * d * m["n_experts"]             # router
                 + flops.gmm_flops(m, m["k"])         # k experts
                 + 2 * 3 * d * m["d_dense"])          # dense SwiGLU FFN
    return m["n_layers"] * per_layer + 2 * d * m["vocab"]


def moe_layers(m: dict) -> int:
    """Layers that route tokens: all of them."""
    return m["n_layers"]
