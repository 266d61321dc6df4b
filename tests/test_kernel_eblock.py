"""E-blocked fused dispatch/combine + the GMM tiling autotune table.

Pins the PR-7 seams: buffer-regime selection (`select_e_block`), E-blocked
vs resident-buffer kernel parity (forward + grad, 1- and 8-device), the
over-budget acceptance config running on the pallas backend *without* a
ref fallback, tuned-vs-default GMM tilings, the guard-estimate dedup
(`COMBINE_BLOCK_T`), and the `python -O` survival of the promoted
ValueError guards."""
import json
import logging
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch as dsp
from repro.core.moe import MoEArgs, moe_apply, moe_defs
from repro.common import param as pm
from repro.kernels import backend as bk_lib
from repro.kernels import dispatch as dl
from repro.kernels import gmm as gmm_lib
from repro.kernels import ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MIB = 1024 * 1024


def _mk_plan(t, e, k, cap, seed=0, d=None):
    """Random routed plan + token batch (mirrors test_kernels helper)."""
    rng = np.random.default_rng(seed)
    d = d or 16
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(t, e)), jnp.float32)
    w, eidx = jax.lax.top_k(jax.nn.softmax(logits), k)
    p = dsp.plan(eidx, w, e, cap)
    return x, p


# ---------------------------------------------------------------------------
# regime selection
# ---------------------------------------------------------------------------

def test_select_e_block_resident_when_fits():
    assert dl.select_e_block(8, 16, 16, jnp.float32) is None


def test_select_e_block_picks_power_of_two_slab():
    # 128*128*288 f32 = 18 MiB > DEFAULT_VMEM_LIMIT -> E-blocked, and the
    # chosen slab's double-buffered estimate must fit where 2x doesn't.
    eb = dl.select_e_block(128, 128, 288, jnp.float32, n_tokens=64)
    assert isinstance(eb, int) and eb & (eb - 1) == 0
    assert dl.eblock_vmem_bytes(eb, 128, 288, jnp.float32,
                                64) <= dl.DEFAULT_VMEM_LIMIT
    assert dl.eblock_vmem_bytes(2 * eb, 128, 288, jnp.float32,
                                64) > dl.DEFAULT_VMEM_LIMIT


def test_select_e_block_raises_when_one_expert_slab_too_big():
    with pytest.raises(dl.DispatchVMEMError, match="even E-blocked"):
        dl.select_e_block(4, 1024, 1024, jnp.float32, limit=64)


def test_combine_guard_shares_backend_estimate():
    """ops.combine's guard and the backend's pre-call estimate both derive
    their token-block term from COMBINE_BLOCK_T: a limit that exactly fits
    the backend estimate also passes the kernel-level guard (no regime
    mismatch on borderline shapes)."""
    e, cap, d, t, k = 4, 8, 32, 256, 2
    x, p = _mk_plan(t, e, k, cap, seed=3, d=d)
    buf = dsp.dispatch(x, p)
    limit = dl.vmem_bytes(e, cap, d, jnp.float32,
                          min(dl.COMBINE_BLOCK_T, t), op="combine")
    out = ops.combine(buf, p.weight, p.expert_index, p.position,
                      vmem_limit=limit)     # must not raise at the boundary
    assert out.shape == (t, d)


# ---------------------------------------------------------------------------
# E-blocked vs resident parity (forward + grad)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,e,k,cap,e_block", [
    (64, 8, 2, 16, 2),
    (64, 8, 2, 16, 8),       # one slab == whole buffer
    (33, 6, 2, 8, 4),        # ragged: E not a multiple of e_block
    (128, 16, 4, 8, 1),      # heavy dropping, slab of one
])
def test_eblock_dispatch_combine_match_resident(t, e, k, cap, e_block):
    x, p = _mk_plan(t, e, k, cap, seed=t + e_block)
    kw = dict(n_experts=e, capacity=cap)
    buf0 = ops.dispatch(x, p.expert_index, p.position, **kw)
    bufE = ops.dispatch(x, p.expert_index, p.position, e_block=e_block,
                        **kw)
    np.testing.assert_array_equal(np.asarray(bufE), np.asarray(buf0))
    y0 = ops.combine(buf0, p.weight, p.expert_index, p.position)
    yE = ops.combine(buf0, p.weight, p.expert_index, p.position,
                     e_block=e_block)
    np.testing.assert_allclose(np.asarray(yE), np.asarray(y0), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("e_block", [1, 2, 4])
def test_eblock_grads_match_resident(e_block):
    t, e, k, cap = 48, 6, 2, 12
    x, p = _mk_plan(t, e, k, cap, seed=11)
    w = p.weight

    def loss(x_, w_, eb):
        buf = ops.dispatch(x_, p.expert_index, p.position, n_experts=e,
                           capacity=cap, e_block=eb)
        y = ops.combine(buf, w_, p.expert_index, p.position, e_block=eb)
        return jnp.sum(y * (1.0 + 0.1 * y))

    g0x, g0w = jax.grad(loss, argnums=(0, 1))(x, w, None)
    gEx, gEw = jax.grad(loss, argnums=(0, 1))(x, w, e_block)
    np.testing.assert_allclose(np.asarray(gEx), np.asarray(g0x),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gEw), np.asarray(g0w),
                               rtol=1e-5, atol=1e-6)


def test_full_moe_layer_forced_eblock_matches_ref():
    """Whole-layer parity with the E-blocked kernels forced at a small
    shape: moe_apply(pallas, dispatch_e_block=2) == moe_apply(ref), fwd
    and parameter/input grads."""
    kw = dict(n_experts=6, k=2, d_model=24, d_ff=40, dtype=jnp.float32,
              capacity_factor=2.0, eval_capacity_factor=2.0)
    params = pm.materialize(moe_defs(MoEArgs(**kw)), jax.random.PRNGKey(0))
    params["gate"]["wg"] = 0.5 * jax.random.normal(
        jax.random.PRNGKey(7), params["gate"]["wg"].shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (96, 24))
    aR = MoEArgs(**kw, kernel_backend="ref")
    aP = MoEArgs(**kw, kernel_backend="pallas", dispatch_e_block=2)

    def loss(pr, x_, a):
        return jnp.sum(moe_apply(pr, x_, a, train=False)[0] ** 2)

    y_ref = moe_apply(params, x, aR, train=False)[0]
    y_pal = moe_apply(params, x, aP, train=False)[0]
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-5)
    gR = jax.grad(loss, argnums=(0, 1))(params, x, aR)
    gP = jax.grad(loss, argnums=(0, 1))(params, x, aP)
    for lR, lP in zip(jax.tree_util.tree_leaves(gR),
                      jax.tree_util.tree_leaves(gP)):
        np.testing.assert_allclose(np.asarray(lP), np.asarray(lR),
                                   rtol=2e-3, atol=2e-4)


# ---------------------------------------------------------------------------
# the acceptance config: buffer > DEFAULT_VMEM_LIMIT on the pallas path
# ---------------------------------------------------------------------------

# E=64, cap=144 (cf 2.25 @ T=2048, k=2), d=512 f32: 18 MiB buffer.
BIG = dict(t=2048, e=64, k=2, cap=144, d=512)


def test_over_budget_dispatch_runs_eblocked_no_fallback(caplog):
    """An [E, C, d] buffer past DEFAULT_VMEM_LIMIT runs on the pallas
    backend via the E-blocked kernels — no ref-fallback warning — and the
    dispatch output bit-matches the ref scatter; grads match the resident
    oracle."""
    t, e, k, cap, d = (BIG[z] for z in ("t", "e", "k", "cap", "d"))
    assert dl.vmem_bytes(e, cap, d, jnp.float32) > dl.DEFAULT_VMEM_LIMIT
    x, p = _mk_plan(t, e, k, cap, seed=5, d=d)
    a = MoEArgs(n_experts=e, k=k, d_model=d, d_ff=8, dtype=jnp.float32,
                kernel_backend="pallas")
    bk = bk_lib.get("pallas")
    with caplog.at_level(logging.WARNING, logger="repro.kernels.backend"):
        buf = bk.dispatch(x, p, a)
        y = bk.combine(buf, p, a)
    assert not [r for r in caplog.records if "falling back" in r.message]
    np.testing.assert_array_equal(np.asarray(buf),
                                  np.asarray(dsp.dispatch(x, p)))
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(dsp.combine(buf, p)),
                               rtol=1e-5, atol=1e-5)

    # grad parity vs the jnp oracle at the same (over-budget) shape
    def loss_pal(x_):
        b = bk.dispatch(x_, p, a)
        return jnp.sum(bk.combine(b, p, a) ** 2)

    def loss_ref(x_):
        b = dsp.dispatch(x_, p)
        return jnp.sum(dsp.combine(b, p) ** 2)

    gP = jax.grad(loss_pal)(x)
    gR = jax.grad(loss_ref)(x)
    np.testing.assert_allclose(np.asarray(gP), np.asarray(gR),
                               rtol=2e-4, atol=2e-5)


def test_over_budget_full_layer_pallas_matches_ref(caplog):
    """The full MoE layer at the over-budget shape: pallas (E-blocked
    dispatch/combine + tuned-tile GMMs) vs ref, forward + grads, with no
    ref-fallback warning.  The committed tuning table carries this
    config's GMM shapes, so the interpret-mode cost stays test-sized."""
    t, e, k, cap, d = (BIG[z] for z in ("t", "e", "k", "cap", "d"))
    kw = dict(n_experts=e, k=k, d_model=d, d_ff=8, dtype=jnp.float32,
              capacity_factor=2.25, eval_capacity_factor=2.25)
    params = pm.materialize(moe_defs(MoEArgs(**kw)), jax.random.PRNGKey(0))
    params["gate"]["wg"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(3), params["gate"]["wg"].shape)
    x = jax.random.normal(jax.random.PRNGKey(2), (t, d)) * 0.1
    aR = MoEArgs(**kw, kernel_backend="ref")
    aP = MoEArgs(**kw, kernel_backend="pallas")
    # the router must actually produce the over-budget buffer shape
    assert dsp.capacity_for(t, e, k, 2.25) == cap

    with caplog.at_level(logging.WARNING, logger="repro.kernels.backend"):
        y_pal = moe_apply(params, x, aP, train=False)[0]
    assert not [r for r in caplog.records if "falling back" in r.message]
    y_ref = moe_apply(params, x, aR, train=False)[0]
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-5)

    def loss(pr, a):
        return jnp.mean(moe_apply(pr, x, a, train=False)[0] ** 2)

    gR = jax.grad(loss)(params, aR)
    gP = jax.grad(loss)(params, aP)
    for lR, lP in zip(jax.tree_util.tree_leaves(gR),
                      jax.tree_util.tree_leaves(gP)):
        np.testing.assert_allclose(np.asarray(lP), np.asarray(lR),
                                   rtol=2e-3, atol=2e-4)


# ---------------------------------------------------------------------------
# GMM tiling autotune
# ---------------------------------------------------------------------------

def test_tuning_table_lookup_and_precedence(tmp_path, monkeypatch):
    path = tmp_path / "tunings.json"
    key = gmm_lib.tuning_key(4, 256, 64, 96, jnp.float32)
    path.write_text(json.dumps({"_meta": {"note": "test"},
                                key: [256, 128, 128]}))
    monkeypatch.setenv(gmm_lib.TUNINGS_ENV, str(path))
    # tuned entry wins when tiles are unset
    bp = gmm_lib.plan_blocks(4, 256, 64, 96, jnp.float32)
    assert (bp.bm, bp.bn, bp.bk, bp.source) == (256, 128, 128, "table")
    # explicit arguments beat the table
    bp = gmm_lib.plan_blocks(4, 256, 64, 96, jnp.float32, bm=128, bn=128,
                             bk=128)
    assert bp.bm == 128
    assert bp.source == "explicit"
    # unknown shape -> the rule's tiles
    bp = gmm_lib.plan_blocks(4, 256, 128, 96, jnp.float32)
    assert (bp.bm, bp.bn, bp.bk) == gmm_lib.rule_tiles(
        4, 256, 128, 96, "float32", gmm_lib.platform.DEFAULT_VMEM_LIMIT)
    assert bp.source == "rule"
    # autotune off pins the static tiles, table or not
    for shape in ((4, 256, 64, 96), (4, 256, 128, 96)):
        bp = gmm_lib.plan_blocks(*shape, jnp.float32, autotune=False)
        assert (bp.bm, bp.bn, bp.bk, bp.source) == (128, 128, 128, "pinned")
    # metadata keys are not tilings
    assert "_meta" not in gmm_lib.load_tunings(str(path))


# tol: the forward's; grads get 10x.  f32 partial sums over K differ only
# in order (~K * eps * |y|: 1e-5 at K = 72, 1e-4 at K = 300, |y| <~ 60);
# a bf16 output may land one ulp (2^-8) apart.
@pytest.mark.parametrize("source,e,c,k,n,dtype,tol", [
    ("table", 5, 136, 72, 80, jnp.float32, 1e-5),
    ("rule", 3, 200, 300, 260, jnp.float32, 1e-4),   # ragged on every dim
    ("rule", 2, 40, 520, 1000, jnp.bfloat16, 1e-2),
])
def test_gmm_tuned_tiles_match_default(tmp_path, monkeypatch, source, e, c,
                                       k, n, dtype, tol):
    """Resolved tiles (a table entry, or the rule's on ragged shapes)
    change the tile walk, never the numbers: fwd + grad parity against
    static 128^3 tiles.  (Unique operand dims so the None-tile jit cache
    can't have been primed with a different table.)"""
    table = ({gmm_lib.tuning_key(e, c, k, n, dtype): [136, 128, 128]}
             if source == "table" else {})
    path = tmp_path / "tunings.json"
    path.write_text(json.dumps(table))
    monkeypatch.setenv(gmm_lib.TUNINGS_ENV, str(path))
    bp = gmm_lib.plan_blocks(e, c, k, n, dtype)
    assert bp.source == source and (bp.bm, bp.bn, bp.bk) != (128, 128, 128)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(e, c, k)), dtype)
    w = jnp.asarray(rng.normal(size=(e, k, n)), dtype)

    def loss(x_, w_, **tiles):
        y = ops.gmm(x_, w_, activation="relu", **tiles)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    y_tuned = ops.gmm(x, w, activation="relu")            # resolved
    y_def = ops.gmm(x, w, activation="relu", bm=128, bn=128, bk=128)
    np.testing.assert_allclose(np.asarray(y_tuned, np.float32),
                               np.asarray(y_def, np.float32),
                               rtol=tol, atol=tol)
    gt = jax.grad(loss, argnums=(0, 1))(x, w)
    gd = jax.grad(loss, argnums=(0, 1))(x, w, bm=128, bn=128, bk=128)
    for a_, b_ in zip(gt, gd):
        np.testing.assert_allclose(np.asarray(a_, np.float32),
                                   np.asarray(b_, np.float32),
                                   rtol=10 * tol, atol=10 * tol)


# The expert GMMs of the benchmark cells, bf16: kimi-k2 widths (d 7168,
# experts 2048 wide) at a 512-token prefill chunk (C = 512) and a 32-slot
# decode step (C = 32), 16 experts; arctic widths (d 7168, experts 4864
# wide) in a 2 x 4096-token train step, 8 experts at C = T, with the dw
# shapes of its backward pass.
BENCH_GMM_SHAPES = {
    "chat-prefill-up": (16, 512, 7168, 2048),
    "chat-prefill-down": (16, 512, 2048, 7168),
    "chat-decode-up": (16, 32, 7168, 2048),
    "chat-decode-down": (16, 32, 2048, 7168),
    "train-up": (8, 8192, 7168, 4864),
    "train-down": (8, 8192, 4864, 7168),
    "train-dw-up": (8, 7168, 8192, 4864),
    "train-dw-down": (8, 4864, 8192, 7168),
}


@pytest.mark.parametrize("case", sorted(BENCH_GMM_SHAPES))
def test_rule_tiles_bench_shapes(case):
    """The rule tiles the benchmark's bf16 shapes without padding, inside
    the VMEM budget by the kernel's own estimate, and in far fewer grid
    steps than the 128^3 walk (>= 100x for the train shapes)."""
    e, c, k, n = BENCH_GMM_SHAPES[case]
    bp = gmm_lib.plan_blocks(e, c, k, n, jnp.bfloat16)
    assert bp.source == "rule"
    assert (bp.c, bp.k, bp.n) == (c, k, n)
    assert gmm_lib.vmem_bytes(bp.bm, bp.bn, bp.bk, jnp.bfloat16) \
        <= gmm_lib.platform.DEFAULT_VMEM_LIMIT
    pinned = gmm_lib.plan_blocks(e, c, k, n, jnp.bfloat16, autotune=False)
    ratio = np.prod(pinned.grid) / np.prod(bp.grid)
    assert ratio >= (100 if case.startswith("train") else 10), ratio
    assert gmm_lib.plan_seconds(e, c, k, n, bp.bm, bp.bn, bp.bk,
                                jnp.bfloat16) \
        < gmm_lib.plan_seconds(e, c, k, n, 128, 128, 128, jnp.bfloat16) / 10


def test_rule_tiles_fit_budget_and_pay_for_padding():
    """The rule is pure in (E, C, K, N, dtype, budget), fits the budget it
    is given, and pads past the 128 / sublane rounding only where the
    model says the copy pays."""
    limit = gmm_lib.platform.DEFAULT_VMEM_LIMIT
    for shape in ((8, 8192, 7168, 4864), (3, 200, 300, 260),
                  (1, 4100, 4100, 4100)):
        for budget in (limit, limit // 4):
            tiles = gmm_lib.rule_tiles(*shape, "bfloat16", budget)
            assert tiles == gmm_lib.rule_tiles(*shape, "bfloat16", budget)
            assert gmm_lib.vmem_bytes(*tiles, jnp.bfloat16) <= budget
    # 4100 rows round to 4112 = 16 * 257 (sublane) and 4100 columns to
    # 4224 = 128 * 33: the only tiles that pad no further are 16 or 4112
    # rows by 128 x {1, 3, 11, 33} columns.  The rule pads the rows past
    # that rounding because the model says the copy pays, so its plan
    # beats every tile that pads no further.
    e, c, k, n = 1, 4100, 4100, 4100
    bp = gmm_lib.plan_blocks(e, c, k, n, jnp.bfloat16)
    assert bp.c > 4112
    no_pad = min(
        gmm_lib.plan_seconds(e, c, k, n, bm, bn, bk, jnp.bfloat16)
        for bm in (16, 4112) for bn in (128, 384, 1408, 4224)
        for bk in (128, 384, 1408, 4224)
        if gmm_lib.vmem_bytes(bm, bn, bk, jnp.bfloat16) <= limit)
    assert gmm_lib.plan_seconds(e, c, k, n, bp.bm, bp.bn, bp.bk,
                                jnp.bfloat16) < no_pad
    with pytest.raises(ValueError, match="fits"):
        gmm_lib.rule_tiles(1, 64, 128, 128, "float32", 1024)


def test_plan_sources_counts_each_path(tmp_path, monkeypatch):
    """Each traced GMM counts how its plan was resolved."""
    e, c, k, n = 2, 24, 40, 56
    path = tmp_path / "tunings.json"
    path.write_text(json.dumps(
        {gmm_lib.tuning_key(e, c, k, n, jnp.float32): [24, 128, 128]}))
    monkeypatch.setenv(gmm_lib.TUNINGS_ENV, str(path))
    x = jnp.ones((e, c, k), jnp.float32)
    w = jnp.ones((e, k, n), jnp.float32)
    x2 = jnp.ones((e, c + 8, k), jnp.float32)      # no table entry
    for source, call in (
            ("explicit", lambda: ops.gmm(x, w, bm=8, bn=128, bk=128)),
            ("table", lambda: ops.gmm(x, w)),
            ("rule", lambda: ops.gmm(x2, w)),
            ("pinned", lambda: ops.gmm(x2, w, autotune=False))):
        before = gmm_lib.plan_sources()
        call()
        after = gmm_lib.plan_sources()
        assert after.get(source, 0) == before.get(source, 0) + 1, source
        assert sum(after.values()) == sum(before.values()) + 1


def test_committed_tuning_table_is_valid():
    """The repo ships a measured table (make tune-kernels); it must parse
    and hold (bm, bn, bk) int triples keyed by ExCxKxNxdtype."""
    table = gmm_lib.load_tunings(
        os.path.join(REPO, "src", "repro", "kernels", "gmm_tunings.json"))
    assert table, "committed gmm_tunings.json is missing or empty"
    for key, tiles in table.items():
        dims = key.split("x")
        assert len(dims) == 5, key
        assert len(tiles) == 3
        assert all(isinstance(v, int) and v > 0 for v in tiles)


# ---------------------------------------------------------------------------
# 8-device mesh: EP schedule with E-blocking + tuned tilings (subprocess)
# ---------------------------------------------------------------------------

def _run(body: str, n_devices: int = 8, env_extra: dict | None = None
         ) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={n_devices}")
        import jax, jax.numpy as jnp, numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               **(env_extra or {}))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_ep_eblock_and_tuned_gmm_8device(tmp_path):
    """The explicit all-to-all EP schedule on 8 fake devices with (a) the
    E-blocked dispatch/combine forced and (b) a tuning table blanketing
    the local GMM shapes with large tiles — both match the ref backend."""
    # Blanket table: big tiles for every plausible local (e, c, k, n) so
    # whatever per-shard shape the EP body hands the GMM resolves tuned.
    table = {}
    for e_ in (1, 2, 4, 8):
        for c_ in (8, 16, 32, 64, 128, 256, 512, 1024):
            for k_ in (16, 36):
                for n_ in (16, 36):
                    table[gmm_lib.tuning_key(e_, c_, k_, n_,
                                             jnp.float32)] = [1024, 512,
                                                              512]
    path = tmp_path / "blanket_tunings.json"
    path.write_text(json.dumps(table))
    out = _run("""
        from repro.common import param as pm
        from repro.core.moe import MoEArgs, moe_defs
        from repro.core.expert_parallel import moe_apply_ep
        from repro.sharding import context
        mesh = context.make_mesh((2, 4), ("data", "model"))
        ctx = context.MeshContext.for_mesh(mesh, "dp_tp_ep")
        kw = dict(n_experts=8, k=2, d_model=16, d_ff=36,
                  dtype=jnp.float32, capacity_factor=8.0,
                  eval_capacity_factor=8.0)
        params = pm.materialize(moe_defs(MoEArgs(**kw)),
                                jax.random.PRNGKey(0))
        params["gate"]["wg"] = 0.5 * jax.random.normal(
            jax.random.PRNGKey(7), params["gate"]["wg"].shape)
        x = jax.random.normal(jax.random.PRNGKey(1), (128, 16))
        def run(a):
            return jax.jit(lambda p, x: moe_apply_ep(
                p, x, a, train=False, ctx=ctx))(params, x)[0]
        y_ref = run(MoEArgs(**kw, kernel_backend="ref"))
        y_eb = run(MoEArgs(**kw, kernel_backend="pallas",
                           dispatch_e_block=2))
        np.testing.assert_allclose(np.asarray(y_eb), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-5)
        print("EP_EBLOCK_OK")
        y_tuned = run(MoEArgs(**kw, kernel_backend="pallas"))
        y_static = run(MoEArgs(**kw, kernel_backend="pallas",
                               gmm_autotune=False))
        np.testing.assert_allclose(np.asarray(y_tuned),
                                   np.asarray(y_static),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(y_tuned),
                                   np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-5)
        print("EP_TUNED_OK")
    """, env_extra={gmm_lib.TUNINGS_ENV: str(path)})
    assert "EP_EBLOCK_OK" in out and "EP_TUNED_OK" in out


# ---------------------------------------------------------------------------
# python -O: the promoted guards must be real exceptions
# ---------------------------------------------------------------------------

def test_promoted_guards_survive_python_O():
    """Under `python -O` asserts vanish; the PR-7 promotions (gmm
    activation guards, top-k k<=E, Scheduler.admit chunking guard) must
    still raise ValueError."""
    script = textwrap.dedent("""
        if __debug__:
            raise SystemExit("must run under -O")
        import jax.numpy as jnp
        hits = []
        from repro.kernels import gmm
        for fn in (gmm._act, gmm._act_grad):
            try:
                fn(jnp.ones((2,)), "tanh")
            except ValueError:
                hits.append("act")
        from repro.kernels import topk_gating as tk
        try:
            tk._topk_raw(jnp.ones((4, 3)), 3, 1, 256, True)
        except ValueError:
            hits.append("topk")
        from repro.serve.scheduler import Scheduler, RequestQueue
        try:
            Scheduler(2, prefill_chunk=8).admit(RequestQueue(), 0)
        except ValueError:
            hits.append("admit")
        print("HITS=" + ",".join(hits))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-O", "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "HITS=act,act,topk,admit" in out.stdout
