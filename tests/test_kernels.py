"""Pallas kernels vs pure-jnp oracles: the parity suite.

Sweeps (E, C, d, d_ff, k, dtype, activation) — including non-tile-aligned
C/d_ff shapes, which exercise the block-plan padding — in interpret mode on
CPU; the identical kernel bodies compile for TPU.  Gradient parity lives in
test_kernel_grads.py, backend wiring in test_kernel_backend.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch as dsp
from repro.kernels import ops, ref
from repro.kernels.gmm import plan_blocks

# (E, C, K, N): MXU-aligned shapes plus deliberately ragged ones that only
# work through the padding path (100, 96, 56, 72, 40, 33 ...).
SHAPES = [
    (4, 128, 128, 128),
    (2, 256, 384, 256),
    (3, 128, 256, 512),
    (1, 512, 128, 128),
    (2, 100, 96, 160),          # ragged C / K / N
    (3, 56, 72, 40),
    (1, 8, 16, 24),             # tiny: blocks clamp to the problem
    (5, 136, 48, 264),          # just past one tile
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return 3e-2 if dtype == jnp.bfloat16 else 2e-3


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("act", ["none", "relu", "silu"])
def test_gmm_allclose(shape, dtype, act):
    e, c, k, n = shape
    x = jax.random.normal(jax.random.PRNGKey(0), (e, c, k), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (e, k, n), dtype)
    got = ops.gmm(x, w, activation=act)
    assert got.shape == (e, c, n) and got.dtype == dtype
    want = ref.gmm_ref(x, w, activation=act)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("block", [(64, 128, 128), (128, 64, 128),
                                   (128, 128, 64), (32, 32, 32)])
def test_gmm_block_shape_independence(block):
    bm, bn, bk = block
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 128))
    got = ops.gmm(x, w, bm=bm, bn=bn, bk=bk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.gmm_ref(x, w)),
                               rtol=2e-3, atol=2e-3)


def test_gmm_padding_is_invisible():
    """A ragged problem equals the same problem manually zero-padded."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 100, 72))
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 72, 90))
    got = ops.gmm(x, w, activation="relu")
    xp = jnp.pad(x, ((0, 0), (0, 28), (0, 56)))
    wp = jnp.pad(w, ((0, 0), (0, 56), (0, 38)))
    padded = ops.gmm(xp, wp, activation="relu")[:, :100, :90]
    np.testing.assert_allclose(np.asarray(got), np.asarray(padded),
                               rtol=1e-5, atol=1e-5)


def test_plan_blocks_pads_to_tiles():
    bp = plan_blocks(3, 100, 72, 90, jnp.float32)
    assert bp.c % bp.bm == 0 and bp.k % bp.bk == 0 and bp.n % bp.bn == 0
    assert bp.c >= 100 and bp.k >= 72 and bp.n >= 90
    assert bp.c % 8 == 0 and bp.grid[0] == 3
    # bf16 sublane tile is 16
    assert plan_blocks(1, 20, 128, 128, jnp.bfloat16).c % 16 == 0
    # aligned shapes don't pad
    bp = plan_blocks(4, 256, 128, 512, jnp.float32)
    assert (bp.c, bp.k, bp.n) == (256, 128, 512)


@pytest.mark.parametrize("e,c,d,f", [(4, 128, 128, 256),   # aligned
                                     (3, 72, 48, 100)])    # ragged
@pytest.mark.parametrize("gated", [False, True])
def test_expert_ffn_fused(e, c, d, f, gated):
    x = jax.random.normal(jax.random.PRNGKey(0), (e, c, d))
    w1 = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (e, d, f))
    w2 = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (e, f, d))
    if gated:
        w3 = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (e, d, f))
        got = ops.expert_ffn({"w1": w1, "w2": w2, "w3": w3}, x,
                             activation="swiglu")
        want = ref.expert_ffn_ref(x, w1, w2, w3)
    else:
        got = ops.expert_ffn({"w1": w1, "w2": w2}, x, activation="relu")
        want = ref.expert_ffn_ref(x, w1, w2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", DTYPES)
def test_expert_ffn_dtypes(dtype):
    e, c, d, f = 2, 64, 32, 48
    x = jax.random.normal(jax.random.PRNGKey(0), (e, c, d), dtype)
    w1 = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (e, d, f), dtype)
    w2 = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (e, f, d), dtype)
    got = ops.expert_ffn({"w1": w1, "w2": w2}, x, activation="relu")
    assert got.dtype == dtype
    want = ref.expert_ffn_ref(x, w1, w2)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# top-k gating
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,e,k", [(256, 64, 4), (512, 384, 8), (256, 8, 2),
                                   (100, 16, 4), (37, 12, 3)])  # ragged T
def test_topk_gating_kernel(t, e, k):
    logits = jax.random.normal(jax.random.PRNGKey(0), (t, e))
    w, idx = ops.topk_gating(logits, k)
    rw, ridx, _ = ref.topk_gating_ref(logits, k)
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))


@pytest.mark.parametrize("extra", [1, 2])
def test_topk_gating_full_raw_values(extra):
    """The k+extra raw values/indices match lax.top_k (load-estimator
    inputs: the (k+1)-th noisy logit is the Appendix-A threshold)."""
    t, e, k = 64, 32, 4
    logits = jax.random.normal(jax.random.PRNGKey(1), (t, e))
    w, idx, vals = ops.topk_gating_full(logits, k, extra=extra)
    tv, ti = jax.lax.top_k(logits, k + extra)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(tv), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ti))
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(jax.nn.softmax(tv[:, :k], axis=-1)),
        rtol=1e-5, atol=1e-6)


def test_topk_gating_ties_stable():
    logits = jnp.zeros((8, 16))
    w, idx = ops.topk_gating(logits, 2)
    # all-equal logits (the zero-initialised gate): uniform weights, the
    # first indices win — lax.top_k's order
    np.testing.assert_allclose(np.asarray(w), 0.5, rtol=1e-6)
    assert (np.asarray(idx) == np.array([0, 1])).all()
    # partial ties (values on a coarse grid; + 0.0 turns -0.0, which
    # lax.top_k orders below +0.0, into +0.0): lax.top_k's order
    coarse = jnp.round(jax.random.normal(jax.random.PRNGKey(4),
                                         (64, 16))) + 0.0
    _, idx = ops.topk_gating(coarse, 8)
    np.testing.assert_array_equal(np.asarray(idx),
                                  np.asarray(jax.lax.top_k(coarse, 8)[1]))


# ---------------------------------------------------------------------------
# fused dispatch/combine scatter
# ---------------------------------------------------------------------------

def _mk_plan(t, e, k, cap, seed=0):
    eidx = jax.random.randint(jax.random.PRNGKey(seed), (t, k), 0, e)
    wt = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(seed + 1), (t, k)), axis=-1)
    return dsp.plan(eidx, wt, e, cap)


@pytest.mark.parametrize("t,e,k,cap", [(64, 8, 2, 32), (33, 6, 2, 8),
                                       (128, 16, 4, 8),   # heavy dropping
                                       (100, 4, 1, 64)])
def test_fused_dispatch_matches_scatter(t, e, k, cap):
    x = jax.random.normal(jax.random.PRNGKey(2), (t, 16))
    p = _mk_plan(t, e, k, cap)
    got = ops.dispatch(x, p.expert_index, p.position, n_experts=e,
                       capacity=cap)
    want = dsp.dispatch(x, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("t,e,k,cap", [(64, 8, 2, 32), (33, 6, 2, 8),
                                       (128, 16, 4, 8)])
def test_fused_combine_matches_gather(t, e, k, cap):
    p = _mk_plan(t, e, k, cap, seed=3)
    buf = jax.random.normal(jax.random.PRNGKey(4), (e, cap, 16))
    got = ops.combine(buf, p.weight, p.expert_index, p.position)
    want = dsp.combine(buf, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_fused_roundtrip_matches_einsum_path():
    """dispatch ∘ expert-identity ∘ combine equals the GShard one-hot
    einsum oracle end-to-end."""
    t, e, k, cap = 48, 4, 2, 32
    x = jax.random.normal(jax.random.PRNGKey(5), (t, 8))
    p = _mk_plan(t, e, k, cap, seed=6)
    buf = ops.dispatch(x, p.expert_index, p.position, n_experts=e,
                       capacity=cap)
    y = ops.combine(buf, p.weight, p.expert_index, p.position)
    want = dsp.combine_einsum(dsp.dispatch_einsum(x, p), p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
