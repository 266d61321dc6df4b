"""The plain reference: the model's mathematics in float32 ``jax.numpy``.

It imports nothing of the program and takes nothing the program made: it
reads its weights from ``bench/weights.py`` (the seed's values, in their
bf16 storage type, which float32 holds exactly) and its tokens from the
benchmark's own generators.  Every matrix product runs at
``precision="highest"``: a float32 product on a TPU otherwise rounds its
inputs to bfloat16.

The layers are the configuration's architecture's
(``bench/archs/<arch>.py``): its module states each layer's mathematics,
and may give layers of different kinds by index.  This module keeps what
the architectures share and the loop around them:

    x = embed(tokens) ; x = layer_i(x) for each layer i ; rmsnorm
    MoE (``moe``): logits = h Wg ; top-k ; softmax over the k ; an expert
         takes the first C assignments in batch order, the rest are
         dropped ; y = sum_k w_k SwiGLU_k(h)
    loss = mean token cross-entropy + sum over layers of
           w_imp CV^2(sum_t gates) + w_load CV^2(assignment counts)

Memory: activations are recomputed per layer, and the experts run one
at a time (the architectures' attention a block of queries at a time),
so a reference at the cells' sizes fits beside nothing else on one chip.

``control="fp8"`` rounds every weight matrix to float8 e4m3, with a
scale per output channel, where it is used (the experts one at a time):
the precision below the configurations' bf16, which a correct
comparison has to tell apart from the program.  Serving's control is
that (fp8 weights are the step a server would take); training's,
``control="fp8_train"``, also rounds every matmul's activation operand,
a scale per row, as fp8 training computes; attention's own products and
the softmaxes stay float32.  Gradients pass straight through the
rounding, in float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
LOSS_CHUNK = 1024
W_IMPORTANCE = 0.1
W_LOAD = 0.1


def _mm(eq, a, b):
    return jnp.einsum(eq, a.astype(F32), b.astype(F32), precision=HI)


def fp8(w, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis`` (the
    contracted dimension), back in float32; the forward pass reads the
    rounded values, the gradient is float32's."""
    w = w.astype(F32)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    # the gradient passes straight through to w in float32: a cast's own
    # gradient would round the cotangent to float8 and flush it to zero
    return w + jax.lax.stop_gradient(q - w)


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, pos, theta):
    """x: [S, H, hd]; rotate-half RoPE at positions pos [S]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _use(w, name, control, axis):
    """A weight as the forward pass reads it: as stored, or rounded to
    fp8 for the control (``axis``: the contracted axis)."""
    if not control:
        return w[name]
    return fp8(w[name], axis)


def _act(x, control, axis=-1):
    """An activation as a matmul reads it: as computed, or, for training's
    control, rounded to fp8 with a scale per row (``axis``: the
    contracted axes)."""
    return fp8(x, axis) if control == "fp8_train" else x


def cv_squared(x):
    return jnp.var(x) / (jnp.mean(x) ** 2 + 1e-10)


def capacity(n_tokens: int, m: dict) -> int:
    """Slots per expert: ceil(k T cf / E), rounded up to a multiple of 8."""
    raw = m["k"] * n_tokens * m["capacity_factor"] / m["n_experts"]
    return int(-(-max(math.ceil(raw), 1) // 8) * 8)


def moe(w, h, m, control=None):
    """h: [T, d] -> (y [T, d], aux loss, kept assignments)."""
    t = h.shape[0]
    e, k = m["n_experts"], m["k"]
    cap = capacity(t, m)
    logits = _mm("td,de->te", _act(h, control), _use(w, "wg", control, 0))
    top, idx = jax.lax.top_k(logits, k)
    gate = jax.nn.softmax(top, axis=-1)                         # [T, k]
    gates = jnp.zeros((t, e), F32).at[jnp.arange(t)[:, None], idx].set(gate)
    aux = (W_IMPORTANCE * cv_squared(gates.sum(0))
           + W_LOAD * cv_squared((gates > 0).astype(F32).sum(0)))
    onehot = jax.nn.one_hot(idx.reshape(-1), e, dtype=jnp.int32)  # [T*k, E]
    rank = (jnp.cumsum(onehot, axis=0) - 1) * onehot
    pos = rank.sum(-1).reshape(t, k)          # place in its expert's queue
    kept = pos < cap
    # Slot (e, c) holds token src[e, c] (t: an empty slot, a zero row)
    # with its gate; each expert gathers its rows, and its weighted output
    # is added into y, one expert at a time, so that no [E, C, d] buffer
    # lives in float32 (nor its gradient).
    tok = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k))
    src = jnp.full((e, cap), t, jnp.int32).at[idx, pos].set(tok, mode="drop")
    wslot = jnp.zeros((e, cap), F32).at[idx, pos].set(gate, mode="drop")
    hpad = jnp.concatenate([h.astype(F32), jnp.zeros((1, h.shape[1]), F32)])

    @jax.checkpoint
    def expert(args):
        s, ws, w1, w3, w2 = args
        if control:     # one expert at a time: [d, f], [d, f], [f, d]
            w1, w3, w2 = fp8(w1, 0), fp8(w3, 0), fp8(w2, 0)
        x = _act(hpad[s], control)
        a = _mm("cd,df->cf", x, w1)
        b = _mm("cd,df->cf", x, w3)
        o = _mm("cf,fd->cd", _act(jax.nn.silu(a) * b, control), w2) * ws[:, None]
        return jnp.zeros_like(hpad).at[s].add(o)[:t]

    y, _ = jax.lax.scan(lambda acc, a: (acc + expert(a), None),
                        jnp.zeros((t, h.shape[1]), F32),
                        (src, wslot, w["w1"], w["w3"], w["w2"]))
    return y, aux, kept.sum()


def mlp(w, h, control=None):
    h = _act(h, control)
    a = _mm("td,df->tf", h, _use(w, "mlp_w1", control, 0))
    b = _mm("td,df->tf", h, _use(w, "mlp_w3", control, 0))
    return _mm("tf,fd->td", _act(jax.nn.silu(a) * b, control),
               _use(w, "mlp_w2", control, 0))


def hidden(arch, params, tokens, m, control=None):
    """tokens [B, S] -> (final normed hidden [B, S, d] f32, aux, kept),
    through the layers of the architecture module ``arch``."""
    x = params["embed"][tokens].astype(F32)
    aux = jnp.zeros((), F32)
    kept = jnp.zeros((), jnp.int32)
    for i in range(m["n_layers"]):
        x, a, kp = jax.checkpoint(
            lambda p, x_: arch.layer(p, i, x_, m, control))(params, x)
        aux, kept = aux + a, kept + kp
    return rmsnorm(x, params["ln_f"], m["eps"]), aux, kept


def _unembed(params, control):
    w = params["unembed"]
    return fp8(w, 0) if control else w


def loss_fn(arch, params, batch, m, control=None):
    """Mean token cross-entropy + balancing losses (the training loss)."""
    x, aux, _ = hidden(arch, params, batch["tokens"], m, control)
    b, s, d = x.shape
    un = _unembed(params, control)
    rows = x.reshape(b * s, d)
    labels = batch["labels"].reshape(b * s)
    c = min(LOSS_CHUNK, b * s)
    n = (b * s) // c

    @jax.checkpoint
    def chunk(args):
        xi, li = args
        logits = _mm("td,dv->tv", _act(xi, control), un)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    total = jax.lax.map(chunk, (rows.reshape(n, c, d), labels.reshape(n, c)))
    xent = jnp.sum(total) / (b * s)
    return xent + aux, xent


@jax.jit
def _embed(params, tokens):
    return params["embed"][tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("arch", "i", "mk", "control"),
                   donate_argnums=(2,))
def _layer_call(arch, params, x, i, mk, control=None):
    return arch.layer(params, i, x, dict(mk), control)[0]


@functools.partial(jax.jit, static_argnames=("mk", "control"))
def _head(params, x, mk, control=None):
    x = rmsnorm(x, params["ln_f"], dict(mk)["eps"])
    return _mm("bsd,dv->bsv", x, _unembed(params, control))[0]


def _logits(arch, params, tokens, mk, control=None):
    """One sequence's logits, a layer per program so that one layer's
    float32 weights are live at a time."""
    x = _embed(params, tokens)
    for i in range(dict(mk)["n_layers"]):
        x = _layer_call(arch, params, x, i, mk, control)
    return _head(params, x, mk, control)


def served_logits(arch, params, seqs, m, control=None, pad_to=None):
    """Logits at the positions that predicted each served token.

    ``seqs``: (prompt, served tokens) pairs.  A sequence is the prompt and
    every served token but the last; position ``len(prompt) - 1 + j``
    predicts served token ``j``.  Each sequence runs alone (its tokens
    route as one call), right-padded to ``pad_to`` so that one program
    serves them all.  Returns one float32 numpy array [n_served, V] each.
    """
    mk = tuple(sorted(m.items()))
    lens = [len(p) + len(t) - 1 for p, t in seqs]
    width = pad_to or max(lens)
    out = []
    for (prompt, served), n in zip(seqs, lens):
        row = np.zeros((1, width), np.int32)
        row[0, :n] = np.concatenate([prompt, served[:-1]])
        lg = _logits(arch, params, jnp.asarray(row), mk, control)
        out.append(np.asarray(lg[len(prompt) - 1:n]))
    return out


# ---------------------------------------------------------------------------
# training: the optimizer the configuration states
# ---------------------------------------------------------------------------

B2 = 0.999
EPS = 1e-8
CLIP = 1.0


def lr_at(step: int, lr: float, warmup: int) -> float:
    """Linear warm-up, then proportional to 1/sqrt(step)."""
    s = max(step, 1)
    return lr * min(s / warmup, math.sqrt(warmup) / math.sqrt(s))


def factored(p) -> bool:
    """Leaves of rank >= 2 keep row and column second moments."""
    return p.ndim >= 2


def init_state(params) -> dict:
    out = {}
    for n, p in params.items():
        if factored(p):
            out[n] = {"vr": jnp.zeros(p.shape[:-1], F32),
                      "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], F32)}
        else:
            out[n] = {"v": jnp.zeros(p.shape, F32)}
    return out


@functools.partial(jax.jit, donate_argnums=(0, 2))
def _update(p, g, s, lr, scale, bias_correction):
    """One leaf's update: Appendix D's factored second moment for leaves
    of rank >= 2 (beta1 = 0), Adam's bias-corrected one otherwise."""
    g = g.astype(F32) * scale
    if "vr" in s:
        g2 = g * g + 1e-30
        vr = B2 * s["vr"] + (1 - B2) * jnp.mean(g2, axis=-1)
        vc = B2 * s["vc"] + (1 - B2) * jnp.mean(g2, axis=-2)
        mean_vr = jnp.maximum(jnp.mean(vr, axis=-1, keepdims=True), 1e-30)
        denom = jnp.sqrt(vr[..., None] * vc[..., None, :]
                         / mean_vr[..., None])
        upd = g / jnp.maximum(denom, EPS)
        new_s = {"vr": vr, "vc": vc}
    else:
        v = B2 * s["v"] + (1 - B2) * g * g
        upd = g / (jnp.sqrt(v / bias_correction) + EPS)
        new_s = {"v": v}
    return (p.astype(F32) - lr * upd).astype(p.dtype), new_s


@functools.partial(jax.jit, static_argnames=("arch", "mk", "control"))
def _grads(arch, params, batch, mk, control=None):
    m = dict(mk)
    (loss, xent), g = jax.value_and_grad(
        lambda p: loss_fn(arch, p, batch, m, control), has_aux=True)(params)
    sq = {n: jnp.sum(jnp.square(v.astype(F32))) for n, v in g.items()}
    return loss, g, sq


def train_steps(arch, params, batches, m, opt, control=None):
    """Follow the program's first steps.  ``opt`` holds lr and warmup.

    Returns losses per step, the first step's clipped gradient norm per
    leaf (what the optimizer received), and the final params (the input
    tree is consumed).  Gradients are stored in each parameter's dtype,
    as the configuration's bf16 parameters hold them; their arithmetic is
    float32."""
    mk = tuple(sorted(m.items()))
    state = init_state(params)
    losses, first_norms = [], None
    for step, batch in enumerate(batches, start=1):
        loss, g, sq = _grads(arch, params, batch, mk, control)
        gnorm = math.sqrt(sum(float(v) for v in sq.values()))
        scale = min(1.0, CLIP / max(gnorm, 1e-9))
        if first_norms is None:
            first_norms = {n: math.sqrt(float(v)) * scale
                           for n, v in sq.items()}
        losses.append(float(loss))
        lr = lr_at(step, opt["learning_rate"], opt["warmup_steps"])
        for n in list(params):
            params[n], state[n] = _update(
                params[n], g.pop(n), state[n], jnp.float32(lr),
                jnp.float32(scale), jnp.float32(1 - B2 ** step))
    return losses, first_norms, params
