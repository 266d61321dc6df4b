"""The kernel backend subsystem: registry resolution, MeshContext-aware
per-shard block specs, and ref-vs-pallas backend equivalence — forward,
one full training step of the small MoE LM, and the 8-device fake-mesh
variants (subprocess, test_distributed-style)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import param as pm
from repro.core.moe import MoEArgs, moe_apply, moe_defs
from repro.data.pipeline import DataConfig, batch_at
from repro.kernels import backend as bk_lib
from repro.models.paper_lm import PaperLMConfig, paper_lm_defs, paper_lm_loss
from repro.optim import optimizers as opt_lib
from repro.sharding import context as ctx_lib
from repro.train.trainer import make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# registry + explicit resolution (the silent-fallback fix)
# ---------------------------------------------------------------------------

def test_registry_has_both_backends():
    assert {"ref", "pallas"} <= set(bk_lib.available())
    assert bk_lib.get("ref").topk_impl is None
    assert bk_lib.get("pallas").topk_impl is not None


def test_unknown_backend_raises_listing_available():
    with pytest.raises(bk_lib.KernelBackendError, match="nope"):
        bk_lib.get("nope")
    with pytest.raises(bk_lib.KernelBackendError, match="pallas"):
        # error message names what IS registered
        bk_lib.get("nope")


def test_broken_backend_reraises_import_error():
    err = ImportError("no pallas on this host")
    bk_lib.register_broken("broken_for_test", err)
    try:
        with pytest.raises(bk_lib.KernelBackendError,
                           match="failed to import"):
            bk_lib.get("broken_for_test")
    finally:
        del bk_lib._REGISTRY["broken_for_test"]


def test_resolve_explicit_and_legacy():
    a = MoEArgs(n_experts=4, k=2, d_model=8, d_ff=16,
                kernel_backend="pallas")
    assert bk_lib.resolve(a).name == "pallas"
    # legacy expert_impl spelling still routes
    a = MoEArgs(n_experts=4, k=2, d_model=8, d_ff=16, expert_impl="pallas")
    assert bk_lib.resolve(a).name == "pallas"
    a = MoEArgs(n_experts=4, k=2, d_model=8, d_ff=16)
    assert bk_lib.resolve(a).name == "ref"


def test_moe_apply_raises_not_degrades_on_bad_backend():
    """The old lazy `from repro.kernels import ops` degraded to the slow
    path with no signal; backend resolution must raise instead."""
    a = MoEArgs(n_experts=4, k=2, d_model=8, d_ff=16, dtype=jnp.float32,
                kernel_backend="does_not_exist")
    params = pm.materialize(moe_defs(a), jax.random.PRNGKey(0))
    x = jnp.ones((16, 8))
    with pytest.raises(bk_lib.KernelBackendError):
        moe_apply(params, x, a, train=False)


def test_trainer_validates_backend_at_construction(tmp_path):
    from repro.data.pipeline import DataIterator
    from repro.train.trainer import Trainer, TrainLoopConfig
    cfg = PaperLMConfig(vocab_size=64, variant="moe", n_experts=4, k=2,
                        d_model=16, expert_hidden=32, dropout=0.0)
    params = pm.materialize(paper_lm_defs(cfg), jax.random.PRNGKey(0))
    kw = dict(
        loss_fn=lambda p, b, r: paper_lm_loss(p, b, cfg, rng=r),
        params=params, oc=opt_lib.OptConfig(),
        loop=TrainLoopConfig(total_steps=1),
        data_iter=DataIterator(DataConfig(vocab_size=64, seq_len=8,
                                          batch_size=4, n_clusters=2)),
        workdir=str(tmp_path))
    with pytest.raises(bk_lib.KernelBackendError):
        Trainer(**kw, kernel_backend="not_a_backend")
    t = Trainer(**kw, kernel_backend="pallas")      # fail-fast path passes
    assert t.kernel_backend == "pallas"


# ---------------------------------------------------------------------------
# MeshContext consumption: per-shard shapes and block specs
# ---------------------------------------------------------------------------

class _FakeMesh:
    """Mesh stand-in: shard_shape/block_plan only read axis names+sizes,
    so an 8-device topology can be faked in the 1-device test process."""
    axis_names = ("data", "model")
    shape = {"data": 2, "model": 4}


def _fake_ctx(manual=True):
    from repro.sharding import partition
    ctx = ctx_lib.MeshContext(mesh=_FakeMesh(),
                              rules=partition.PLANS["dp_tp_ep"])
    return ctx.manual("data", "model") if manual else ctx


def test_shard_shape_divides_by_manual_axes_only():
    ctx = _fake_ctx(manual=True)
    # experts -> model (size 4) is manual: E=8 -> 2 local
    assert bk_lib.shard_shape(ctx, (8, 64, 16),
                              ("experts", "expert_capacity", "embed")) \
        == (2, 64, 16)
    # Auto-mode context (no manual axes): kernels see global shapes
    assert bk_lib.shard_shape(_fake_ctx(manual=False), (8, 64, 16),
                              ("experts", "expert_capacity", "embed")) \
        == (8, 64, 16)
    # non-divisible dims replicate (partition.py fallback semantics)
    assert bk_lib.shard_shape(ctx, (6,), ("experts",)) == (6,)
    # off-mesh: identity
    assert bk_lib.shard_shape(None, (8, 64), ("experts", "embed")) \
        == (8, 64)


def test_block_plan_is_per_shard():
    a = MoEArgs(n_experts=8, k=2, d_model=16, d_ff=100, dtype=jnp.float32)
    ctx = _fake_ctx(manual=True)
    bp = bk_lib.block_plan(a, capacity=72, ctx=ctx)
    assert bp.e == 2                      # 8 experts / model=4
    assert bp.c % bp.bm == 0 and bp.c >= 72      # ragged capacity padded
    assert bp.n % bp.bn == 0 and bp.n >= 100     # ragged d_ff padded
    # off-mesh plan covers the global shape
    assert bk_lib.block_plan(a, capacity=72, ctx=None).e == 8


def test_pallas_expert_ffn_rejects_mismatched_shard():
    a = MoEArgs(n_experts=8, k=2, d_model=16, d_ff=32, dtype=jnp.float32)
    ctx = _fake_ctx(manual=True)          # expects E_local == 2
    x = jnp.ones((3, 8, 16))              # 3 % 2 != 0: not a shard view
    params = {"w1": jnp.ones((3, 16, 32)), "w2": jnp.ones((3, 32, 16))}
    with pytest.raises(bk_lib.KernelBackendError, match="per-shard"):
        bk_lib.get("pallas").expert_ffn(params, x, a, ctx=ctx)


# ---------------------------------------------------------------------------
# backend equivalence: forward + one full training step (1 device)
# ---------------------------------------------------------------------------

MOE_KW = dict(n_experts=8, k=2, d_model=16, d_ff=36, dtype=jnp.float32,
              capacity_factor=2.0)


@pytest.mark.parametrize("train", [False, True])
def test_moe_forward_equivalence(train):
    params = pm.materialize(moe_defs(MoEArgs(**MOE_KW)),
                            jax.random.PRNGKey(0))
    params["gate"]["wg"] = 0.5 * jax.random.normal(jax.random.PRNGKey(7),
                                                   (16, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (100, 16))
    rng = jax.random.PRNGKey(2)
    y_ref, aux_ref = moe_apply(params, x, MoEArgs(**MOE_KW,
                                                  kernel_backend="ref"),
                               train=train, rng=rng)
    y_pal, aux_pal = moe_apply(params, x, MoEArgs(**MOE_KW,
                                                  kernel_backend="pallas"),
                               train=train, rng=rng)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(aux_pal["aux_loss"]),
                               float(aux_ref["aux_loss"]), rtol=1e-4)


def _one_train_step(backend: str, ctx=None, steps: int = 1):
    cfg = PaperLMConfig(vocab_size=64, variant="moe", n_experts=4, k=2,
                        d_model=16, expert_hidden=24,     # ragged d_ff
                        dropout=0.0, kernel_backend=backend)
    params = pm.materialize(paper_lm_defs(cfg), jax.random.PRNGKey(0))
    dc = DataConfig(vocab_size=64, seq_len=16, batch_size=8, n_clusters=4)
    oc = opt_lib.OptConfig(learning_rate=1e-2, warmup_steps=1)
    step = make_train_step(
        lambda p, b, r: paper_lm_loss(p, b, cfg, rng=r, ctx=ctx), oc)
    state = {"params": params, "opt": opt_lib.init(params, oc)}
    rng = jax.random.PRNGKey(3)
    metrics = None
    for i in range(steps):
        state, metrics = jax.jit(step)(state, batch_at(dc, i),
                                       jax.random.fold_in(rng, i))
    return state, metrics


def test_train_step_equivalence_1device():
    """One full training step of the small MoE LM: pallas and ref backends
    produce allclose losses and parameter updates."""
    st_ref, m_ref = _one_train_step("ref")
    st_pal, m_pal = _one_train_step("pallas")
    np.testing.assert_allclose(float(m_pal["loss"]), float(m_ref["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_flatten(st_pal["params"])[0],
                    jax.tree_util.tree_flatten(st_ref["params"])[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_train_step_equivalence_scan_remat_stack():
    """One training step through the *transformer* stack (lax.scan + remat
    — a different AD path than the paper LM) on both backends.

    Regression: the topk kernel's custom_vjp must not expose integer
    outputs; under scan+remat jax linearizes through it and instantiates
    float0 cotangents for int dtypes, which crashed the dispatch plan's
    integer argsort ("Called mul with a float0")."""
    from repro.configs.base import get_config
    from repro.models import lm

    base = get_config("kimi-k2-1t-a32b").replace(
        n_layers=2, d_model=32, vocab_size=64, n_heads=4, n_kv_heads=2,
        head_dim=8, d_ff=48, n_experts=4, moe_k=2, moe_d_ff=24,
        param_dtype=jnp.float32, compute_dtype=jnp.float32,
        q_block=16, kv_block=16)
    dc = DataConfig(vocab_size=64, seq_len=16, batch_size=4, n_clusters=4)
    oc = opt_lib.OptConfig(learning_rate=1e-2, warmup_steps=1)

    def one_step(backend):
        cfg = base.replace(kernel_backend=backend)
        params = pm.materialize(lm.lm_defs(cfg), jax.random.PRNGKey(0))
        step = make_train_step(
            lambda p, b, r: lm.lm_loss(p, b, cfg, rng=r), oc)
        state = {"params": params, "opt": opt_lib.init(params, oc)}
        return jax.jit(step)(state, batch_at(dc, 0), jax.random.PRNGKey(3))

    st_ref, m_ref = one_step("ref")
    st_pal, m_pal = one_step("pallas")
    np.testing.assert_allclose(float(m_pal["loss"]), float(m_ref["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_flatten(st_pal["params"])[0],
                    jax.tree_util.tree_flatten(st_ref["params"])[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# hierarchical MoE through the registry (ROADMAP open item: no more direct
# jnp path) — ref vs pallas parity, forward and gradients
# ---------------------------------------------------------------------------

HMOE_KW = dict(n_groups=4, n_experts_per_group=4, k_primary=2,
               k_secondary=2, d_model=16, d_ff=32, dtype=jnp.float32,
               capacity_factor=4.0)


def _hmoe_setup():
    from repro.core.hierarchical import HMoEArgs, hmoe_defs
    params = pm.materialize(hmoe_defs(HMoEArgs(**HMOE_KW)),
                            jax.random.PRNGKey(0))
    params["gate_primary"]["wg"] = 0.5 * jax.random.normal(
        jax.random.PRNGKey(7), (16, 4))
    params["gate_secondary"]["wg"] = 0.5 * jax.random.normal(
        jax.random.PRNGKey(8), (4, 16, 4))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    return params, x


@pytest.mark.parametrize("train", [False, True])
def test_hmoe_backend_parity(train):
    from repro.core.hierarchical import HMoEArgs, hmoe_apply
    params, x = _hmoe_setup()
    rng = jax.random.PRNGKey(2)
    y_ref, aux_ref = hmoe_apply(params, x,
                                HMoEArgs(**HMOE_KW, kernel_backend="ref"),
                                train=train, rng=rng)
    y_pal, aux_pal = hmoe_apply(params, x,
                                HMoEArgs(**HMOE_KW,
                                         kernel_backend="pallas"),
                                train=train, rng=rng)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(aux_pal["aux_loss"]),
                               float(aux_ref["aux_loss"]), rtol=1e-4)
    # serving telemetry over the flattened (group, expert) grid
    assert aux_ref["telemetry"]["expert_load"].shape == (16,)
    np.testing.assert_allclose(
        np.asarray(aux_pal["telemetry"]["expert_load"]),
        np.asarray(aux_ref["telemetry"]["expert_load"]))


def test_hmoe_backend_grad_parity():
    from repro.core.hierarchical import HMoEArgs, hmoe_apply
    params, x = _hmoe_setup()
    rng = jax.random.PRNGKey(2)

    def loss(p, backend):
        y, aux = hmoe_apply(p, x, HMoEArgs(**HMOE_KW,
                                           kernel_backend=backend),
                            train=True, rng=rng)
        return jnp.sum(y ** 2) + aux["aux_loss"]

    g_ref = jax.grad(lambda p: loss(p, "ref"))(params)
    g_pal = jax.grad(lambda p: loss(p, "pallas"))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_pal)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


def test_hmoe_unknown_backend_raises():
    from repro.core.hierarchical import HMoEArgs, hmoe_apply
    params, x = _hmoe_setup()
    with pytest.raises(bk_lib.KernelBackendError):
        hmoe_apply(params, x,
                   HMoEArgs(**HMOE_KW, kernel_backend="does_not_exist"),
                   train=False)


# ---------------------------------------------------------------------------
# VMEM-footprint guard on the fused dispatch/combine kernel: shapes whose
# single-expert slab exceeds even the E-blocked budget still raise (kernel
# level) / fall back to ref with a warning (backend level); everything
# else now runs fused — see tests/test_kernel_eblock.py
# ---------------------------------------------------------------------------

def test_dispatch_vmem_guard_raises_directly():
    from repro.kernels import dispatch as dl
    # estimate helper: [E, C, d] buffer + token block, in bytes
    assert dl.vmem_bytes(8, 64, 32, jnp.float32) == 8 * 64 * 32 * 4
    with pytest.raises(dl.DispatchVMEMError, match="VMEM"):
        dl.check_vmem(1024, 4096, 4096, jnp.float32, limit=1 << 20)
    x = jnp.ones((16, 8), jnp.float32)
    eidx = jnp.zeros((16, 2), jnp.int32)
    pos = jnp.tile(jnp.arange(2, dtype=jnp.int32)[None], (16, 1))
    with pytest.raises(dl.DispatchVMEMError):
        dl.dispatch(x, eidx, pos, n_experts=4, capacity=8, vmem_limit=16)
    buf = jnp.ones((4, 8, 8), jnp.float32)
    with pytest.raises(dl.DispatchVMEMError):
        dl.combine(buf, jnp.ones((16, 2)), eidx, pos, vmem_limit=16)
    # default limit admits the small shape
    assert dl.dispatch(x, eidx, pos, n_experts=4, capacity=8).shape \
        == (4, 8, 8)


def test_backend_vmem_guard_falls_back_to_ref():
    """Past the configured budget the pallas backend must route
    dispatch/combine to the ref scatter (same numerics) instead of
    OOMing — MoEArgs.dispatch_vmem_limit is the knob."""
    params = pm.materialize(moe_defs(MoEArgs(**MOE_KW)),
                            jax.random.PRNGKey(0))
    params["gate"]["wg"] = 0.5 * jax.random.normal(jax.random.PRNGKey(7),
                                                   (16, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (100, 16))
    y_pal, _ = moe_apply(params, x,
                         MoEArgs(**MOE_KW, kernel_backend="pallas"),
                         train=False)
    before = bk_lib.fallbacks()
    with pytest.warns(RuntimeWarning, match="falling back"):
        y_fb, _ = moe_apply(params, x,
                            MoEArgs(**MOE_KW, kernel_backend="pallas",
                                    dispatch_vmem_limit=64),
                            train=False)
    np.testing.assert_allclose(np.asarray(y_fb), np.asarray(y_pal),
                               rtol=2e-4, atol=2e-5)
    after = bk_lib.fallbacks()
    for site in ("dispatch", "combine"):
        assert after.get(site, 0) == before.get(site, 0) + 1, site


def test_no_fallback_counted_within_budget():
    params = pm.materialize(moe_defs(MoEArgs(**MOE_KW)),
                            jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 16))
    before = bk_lib.fallbacks()
    moe_apply(params, x, MoEArgs(**MOE_KW, kernel_backend="pallas"),
              train=False)
    assert bk_lib.fallbacks() == before


def test_interpret_mode_is_chosen_at_call_time_from_the_platform():
    """None follows the platform (the interpreter off-TPU), explicit
    values win, and no kernel entry point defaults to interpret=True."""
    import inspect
    from repro.kernels import dispatch as dl
    from repro.kernels import fused_decode as fd
    from repro.kernels import gmm as gmm_lib
    from repro.kernels import platform
    from repro.kernels import topk_gating as topk_lib
    assert platform.interpret_mode(None) is (jax.default_backend() != "tpu")
    assert platform.interpret_mode(False) is False
    for fn in (gmm_lib.gmm, topk_lib.topk_gating, topk_lib.topk_gating_full,
               dl.dispatch, dl.combine, fd.decode_step, fd.routed_apply):
        default = inspect.signature(fn).parameters["interpret"].default
        assert default is None, fn


# ---------------------------------------------------------------------------
# 8-device fake mesh (subprocess, like test_distributed.py)
# ---------------------------------------------------------------------------

def _run(body: str, n_devices: int = 8) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={n_devices}")
        import jax, jax.numpy as jnp, numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_train_step_equivalence_8device_mesh():
    """One training step under a (2,4) MeshContext on 8 fake devices:
    pallas vs ref backends agree on loss and updated params."""
    out = _run("""
        from repro.common import param as pm
        from repro.data.pipeline import DataConfig, batch_at
        from repro.models.paper_lm import (PaperLMConfig, paper_lm_defs,
                                           paper_lm_loss)
        from repro.optim import optimizers as opt_lib
        from repro.sharding import context
        from repro.train.trainer import make_train_step
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = context.make_mesh((2, 4), ("data", "model"))
        ctx = context.MeshContext.for_mesh(mesh, "dp_tp_ep")

        def run(backend):
            cfg = PaperLMConfig(vocab_size=64, variant="moe", n_experts=4,
                                k=2, d_model=16, expert_hidden=24,
                                dropout=0.0, kernel_backend=backend)
            params = pm.materialize(paper_lm_defs(cfg),
                                    jax.random.PRNGKey(0))
            params = jax.device_put(params, NamedSharding(mesh, P()))
            dc = DataConfig(vocab_size=64, seq_len=16, batch_size=8,
                            n_clusters=4)
            oc = opt_lib.OptConfig(learning_rate=1e-2, warmup_steps=1)
            step = make_train_step(
                lambda p, b, r: paper_lm_loss(p, b, cfg, rng=r, ctx=ctx),
                oc)
            state = {"params": params, "opt": opt_lib.init(params, oc)}
            batch = jax.device_put(batch_at(dc, 0),
                                   NamedSharding(mesh, P(("data",))))
            return jax.jit(step)(state, batch, jax.random.PRNGKey(3))

        st_ref, m_ref = run("ref")
        st_pal, m_pal = run("pallas")
        np.testing.assert_allclose(float(m_pal["loss"]),
                                   float(m_ref["loss"]), rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_flatten(st_pal["params"])[0],
                        jax.tree_util.tree_flatten(st_ref["params"])[0]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
        print("STEP8_OK")
    """)
    assert "STEP8_OK" in out


def test_expert_parallel_pallas_matches_ref_8device():
    """The explicit all-to-all EP schedule with the pallas backend (ops
    consuming the Manual-mode ctx: [E/ep, ep*C, d] local blocks) matches
    the ref backend and the single-device oracle."""
    out = _run("""
        from repro.common import param as pm
        from repro.core.moe import MoEArgs, moe_defs, moe_apply
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
        y_ref, _ = jax.jit(lambda p, x: moe_apply_ep(
            p, x, MoEArgs(**kw, kernel_backend="ref"), train=False,
            ctx=ctx))(params, x)
        y_pal, _ = jax.jit(lambda p, x: moe_apply_ep(
            p, x, MoEArgs(**kw, kernel_backend="pallas"), train=False,
            ctx=ctx))(params, x)
        np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-5)
        y1, _ = moe_apply(params, x, MoEArgs(**kw), train=False)
        np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y1),
                                   rtol=2e-4, atol=2e-5)
        print("EP_PALLAS_OK")
    """)
    assert "EP_PALLAS_OK" in out
