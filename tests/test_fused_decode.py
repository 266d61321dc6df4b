"""The fused single-launch decode step (kernels/fused_decode.py).

Correctness bar: the fused kernel and the unfused pallas pipeline it
replaces both match the independently-formulated f32 oracle to float
tolerance, report the exact route telemetry, and the fused path collapses
the per-MoE-layer decode hot path from >=4 pallas launches to exactly 1.
Fused and unfused are two different programs over the same math, so the
compiler may reassociate their f32 dots and sums: they agree to float
tolerance, not bit for bit.  Serving-level on/off parity lives in
test_serve.py (test_serve_parity_matrix_fused*).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import param as pm
from repro.core import dispatch as dsp
from repro.core.moe import MoEArgs, moe_apply, moe_defs
from repro.core.router import RouterSpec
from repro.kernels import backend as backend_lib
from repro.kernels import fused_decode as fd
from repro.kernels import ops, ref


def _problem(t=8, d=16, e=4, f=32, k=2, gated=False, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (t, d), jnp.float32)
    wg = jax.random.normal(ks[1], (d, e), jnp.float32) * 0.5
    w1 = jax.random.normal(ks[2], (e, d, f), jnp.float32) * 0.1
    w2 = jax.random.normal(ks[3], (e, f, d), jnp.float32) * 0.1
    w3 = (jax.random.normal(ks[4], (e, d, f), jnp.float32) * 0.1
          if gated else None)
    return x, wg, w1, w2, w3


VALID = np.array([1, 1, 1, 0, 1, 1, 0, 1], np.float32)

# Fused vs unfused vs oracle, all in f32.  Each output element is a
# combine of k=2 expert outputs, each a chain of two f32 dots of width
# d=16 and f=32 over O(1) inputs and 0.1-scaled weights (|y| < 0.5).  The
# paths accumulate those dots in different orders (XLA's einsum vs the
# kernel's dot, on CPU and on the MXU), so elements may differ by a few
# f32 ulps of the partial sums: ~f * eps * max|partial| = 32 * 1.2e-7 *
# 0.5 ~ 2e-6.  Telemetry counts are integers and must match exactly.
F32_RTOL = 1e-5
F32_ATOL = 2e-6


# ---------------------------------------------------------------------------
# kernel vs oracle (independent formulation: lax.top_k + argsort plan)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("activation,gate", [("relu", "random"),
                                             ("swiglu", "random"),
                                             ("swiglu", "zero")])
def test_decode_step_matches_oracle(activation, gate):
    """``gate="zero"`` is the model's initial gate (Appendix A): every
    score ties and the kernel must pick lax.top_k's lowest indices."""
    gated = activation == "swiglu"
    x, wg, w1, w2, w3 = _problem(gated=gated)
    if gate == "zero":
        wg = jnp.zeros_like(wg)
    valid = jnp.asarray(VALID)
    y, load, over = fd.decode_step(x, valid, wg, w1, w2, w3, k=2,
                                   capacity=8, activation=activation)
    yr, lr, ovr = ref.fused_decode_ref(x, wg, w1, w2, w3, valid, k=2,
                                       capacity=8)
    assert y.shape == x.shape and y.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(lr))
    np.testing.assert_array_equal(np.asarray(over), np.asarray(ovr))
    # masked-out tokens produce exactly zero output and route nowhere
    np.testing.assert_array_equal(np.asarray(y)[VALID == 0], 0.0)
    assert int(load.sum()) == int(VALID.sum()) * 2


def test_decode_step_overflow_telemetry_tight_capacity():
    """capacity=1 forces drops; load counts every kept-or-dropped positive
    assignment, overflow exactly the dropped ones (route_telemetry math)."""
    x, wg, w1, w2, _ = _problem()
    valid = jnp.ones((8,), jnp.float32)
    y, load, over = fd.decode_step(x, valid, wg, w1, w2, k=2, capacity=1)
    yr, lr, ovr = ref.fused_decode_ref(x, wg, w1, w2, valid=valid, k=2,
                                       capacity=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(lr))
    np.testing.assert_array_equal(np.asarray(over), np.asarray(ovr))
    assert int(over.sum()) > 0
    assert int((load - over).max()) <= 1      # kept <= capacity per expert


def test_decode_step_validates_arguments():
    x, wg, w1, w2, _ = _problem()
    valid = jnp.ones((8,), jnp.float32)
    with pytest.raises(ValueError, match="w3"):
        fd.decode_step(x, valid, wg, w1, w2, k=2, capacity=8,
                       activation="swiglu")
    with pytest.raises(ValueError, match="k"):
        fd.decode_step(x, valid, wg, w1, w2, k=5, capacity=8)


# ---------------------------------------------------------------------------
# fused vs the unfused pallas pipeline (the launches it replaces), each
# against the f32 oracle
# ---------------------------------------------------------------------------

def _unfused_decode(x, wg, w1, w2, w3, valid, *, k, capacity,
                    activation="relu"):
    """The exact op sequence the fused kernel collapses: pallas top-k
    gating on the clean logits, stable-argsort plan, pallas dispatch /
    expert FFN / combine."""
    logits = jnp.dot(x.astype(jnp.float32), wg.astype(jnp.float32))
    w, idx, _ = ops.topk_gating_full(logits, k)
    w = w * valid.astype(jnp.float32)[:, None]
    p = dsp.plan(idx, w, wg.shape[-1], capacity)
    buf = ops.dispatch(x, p.expert_index, p.position,
                       n_experts=p.n_experts, capacity=capacity)
    params = {"w1": w1, "w2": w2}
    if w3 is not None:
        params["w3"] = w3
    out = ops.expert_ffn(params, buf, activation=activation)
    return ops.combine(out, p.weight, p.expert_index, p.position,
                       out_dtype=x.dtype)


def _assert_f32_close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=F32_RTOL, atol=F32_ATOL)


@pytest.mark.parametrize("activation", ["relu", "swiglu"])
def test_decode_step_bit_exact_vs_unfused(activation):
    """Fused and unfused both match the f32 oracle (and so each other)
    within F32_RTOL/F32_ATOL.  (The name predates the tolerance: the two
    paths differ by up to 6e-8; it is kept so the test id stays stable.)"""
    gated = activation == "swiglu"
    x, wg, w1, w2, w3 = _problem(gated=gated, seed=3)
    valid = jnp.asarray(VALID)
    y, _, _ = fd.decode_step(x, valid, wg, w1, w2, w3, k=2, capacity=8,
                             activation=activation)
    unfused = _unfused_decode(x, wg, w1, w2, w3, valid, k=2, capacity=8,
                              activation=activation)
    oracle, _, _ = ref.fused_decode_ref(x, wg, w1, w2, w3, valid, k=2,
                                        capacity=8)
    _assert_f32_close(y, oracle)
    _assert_f32_close(unfused, oracle)
    _assert_f32_close(y, unfused)


@pytest.mark.parametrize("mode", ["ffn", "proj"])
def test_routed_apply_bit_exact_vs_unfused(mode):
    """Plan-mode kernel (routing done outside — expert_choice, MoA): the
    fused scatter/FFN/combine and the separate pallas launches both match
    the f32 einsum oracle over the same plan.  (Name kept from when this
    asserted bit-identity; see test_decode_step_bit_exact_vs_unfused.)"""
    t, e, k, cap, d = 16, 4, 2, 8, 16
    x = jax.random.normal(jax.random.PRNGKey(5), (t, d), jnp.float32)
    eidx = jax.random.randint(jax.random.PRNGKey(6), (t, k), 0, e)
    wt = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(7), (t, k)),
                        axis=-1)
    p = dsp.plan(eidx, wt, e, cap)
    if mode == "ffn":
        f = 32
        w1 = jax.random.normal(jax.random.PRNGKey(8), (e, d, f)) * 0.1
        w2 = jax.random.normal(jax.random.PRNGKey(9), (e, f, d)) * 0.1
        got = ops.fused_routed_apply(x, p, p, w1, w2, mode="ffn",
                                     activation="relu")
        buf = ops.dispatch(x, p.expert_index, p.position, n_experts=e,
                           capacity=cap)
        out = ops.expert_ffn({"w1": w1, "w2": w2}, buf, activation="relu")
        out_ref = ref.expert_ffn_ref(dsp.dispatch(x, p), w1, w2)
    else:
        d_out = 24
        w = jax.random.normal(jax.random.PRNGKey(8), (e, d, d_out)) * 0.1
        got = ops.fused_routed_apply(x, p, p, w, mode="proj",
                                     out_dtype=x.dtype)
        buf = ops.dispatch(x, p.expert_index, p.position, n_experts=e,
                           capacity=cap)
        out = ops.gmm(buf, w)
        out_ref = ref.gmm_ref(dsp.dispatch(x, p), w)
    unfused = ops.combine(out, p.weight, p.expert_index, p.position,
                          out_dtype=x.dtype)
    oracle = dsp.combine(out_ref, p, dtype=x.dtype)
    _assert_f32_close(got, oracle)
    _assert_f32_close(unfused, oracle)
    _assert_f32_close(got, unfused)


# ---------------------------------------------------------------------------
# backend wiring: moe_apply on/off parity, launch count, VMEM fallback
# ---------------------------------------------------------------------------

MOE_KW = dict(n_experts=4, k=2, d_model=16, d_ff=32, dtype=jnp.float32,
              capacity_factor=2.0)


def _moe_problem(policy="noisy_topk", **over):
    kw = dict(MOE_KW, router=RouterSpec(policy=policy, capacity_factor=2.0),
              **over)
    params = pm.materialize(moe_defs(MoEArgs(**kw)), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, kw["d_model"]),
                          jnp.float32)
    mask = jnp.asarray(VALID)
    return kw, params, x, mask


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("policy", ["noisy_topk", "expert_choice"])
def test_moe_apply_fused_decode_parity(policy, backend):
    """moe_apply(train=False) with fused_decode on matches the unfused
    path and the f32 ref layer (within F32_RTOL/F32_ATOL) and reports the
    same telemetry, for both router policies (full-fusion vs plan-mode
    kernels) on both backends."""
    kw, params, x, mask = _moe_problem(policy, kernel_backend=backend)
    y0, aux0 = moe_apply(params, x, MoEArgs(**kw), train=False, mask=mask)
    y1, aux1 = moe_apply(params, x, MoEArgs(**kw, fused_decode=True),
                         train=False, mask=mask)
    kw_ref = dict(kw, kernel_backend="ref")
    oracle, _ = moe_apply(params, x, MoEArgs(**kw_ref), train=False,
                          mask=mask)
    _assert_f32_close(y0, oracle)
    _assert_f32_close(y1, oracle)
    _assert_f32_close(y1, y0)
    for key in ("expert_load", "overflow"):
        np.testing.assert_array_equal(np.asarray(aux0["telemetry"][key]),
                                      np.asarray(aux1["telemetry"][key]))
    # decode consumers read telemetry only; the fused branch's aux_loss
    # and balance metrics are inert zeros
    assert float(aux1["aux_loss"]) == 0.0


def test_fused_decode_ignored_under_train():
    kw, params, x, mask = _moe_problem(kernel_backend="pallas")
    y0, aux0 = moe_apply(params, x, MoEArgs(**kw), train=True,
                         rng=jax.random.PRNGKey(2), mask=mask)
    y1, aux1 = moe_apply(params, x, MoEArgs(**kw, fused_decode=True),
                         train=True, rng=jax.random.PRNGKey(2), mask=mask)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(aux0["aux_loss"]),
                                  np.asarray(aux1["aux_loss"]))


def _count_launches(fn, monkeypatch):
    import jax.experimental.pallas as pl
    count = [0]
    real = pl.pallas_call

    def counting(*args, **kwargs):
        count[0] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", counting)
    jax.clear_caches()
    try:
        jax.block_until_ready(fn())
    finally:
        jax.clear_caches()
    return count[0]


def test_fused_decode_single_launch(monkeypatch):
    """The acceptance criterion: >=4 pallas launches per MoE decode layer
    (top-k, dispatch, 2x GMM, combine) collapse to exactly 1."""
    kw, params, x, mask = _moe_problem(kernel_backend="pallas")
    unfused = _count_launches(
        lambda: moe_apply(params, x, MoEArgs(**kw), train=False,
                          mask=mask)[0], monkeypatch)
    fused = _count_launches(
        lambda: moe_apply(params, x, MoEArgs(**kw, fused_decode=True),
                          train=False, mask=mask)[0], monkeypatch)
    assert unfused >= 4, unfused
    assert fused == 1, fused


def test_fused_decode_vmem_fallback_warns_and_matches(monkeypatch):
    """Past the slab budget the pallas backend falls back *loudly* to the
    unfused pipeline (the dispatch VMEM fallback pattern) — same output."""
    kw, params, x, mask = _moe_problem(kernel_backend="pallas")
    tiny = MoEArgs(**kw, fused_decode=True, dispatch_vmem_limit=1024)
    before = backend_lib.fallbacks().get("decode_step", 0)
    with pytest.warns(RuntimeWarning, match="fused slab"):
        y1, aux1 = moe_apply(params, x, tiny, train=False, mask=mask)
    assert backend_lib.fallbacks()["decode_step"] == before + 1
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        y0, _ = moe_apply(params, x, MoEArgs(**kw), train=False, mask=mask)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    for key in ("expert_load", "overflow"):
        assert key in aux1["telemetry"]


def test_vmem_estimates_scale():
    relu = fd.decode_vmem_bytes(8, 16, 32, 4, 8, jnp.float32, jnp.float32)
    gated = fd.decode_vmem_bytes(8, 16, 32, 4, 8, jnp.float32, jnp.float32,
                                 gated=True)
    assert 0 < relu < gated
    proj = fd.routed_vmem_bytes(8, 16, 24, 0, 4, 8, jnp.float32,
                                jnp.float32, mode="proj")
    ffn = fd.routed_vmem_bytes(8, 16, 16, 32, 4, 8, jnp.float32,
                               jnp.float32, mode="ffn")
    assert 0 < proj and 0 < ffn
