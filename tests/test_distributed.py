"""Multi-device tests.  The pytest process owns 1 CPU device, so these
spawn subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
(the same trick dryrun.py uses at 512)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, n_devices: int = 8) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={n_devices}")
        import jax, jax.numpy as jnp, numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_shard_map_ep_matches_reference():
    """The paper's §3.1 explicit all-to-all EP schedule must agree with the
    single-device MoE (combined-batch semantics)."""
    out = _run("""
        from repro.common import param as pm
        from repro.core.moe import MoEArgs, moe_defs, moe_apply
        from repro.core.expert_parallel import moe_apply_ep
        from repro.sharding import context
        mesh = context.make_mesh((2, 4), ("data", "model"))
        ctx = context.MeshContext.for_mesh(mesh, "dp_tp_ep")
        a = MoEArgs(n_experts=8, k=2, d_model=16, d_ff=32,
                    dtype=jnp.float32, capacity_factor=8.0,
                    eval_capacity_factor=8.0)
        params = pm.materialize(moe_defs(a), jax.random.PRNGKey(0))
        params["gate"]["wg"] = 0.5 * jax.random.normal(
            jax.random.PRNGKey(7), params["gate"]["wg"].shape)
        x = jax.random.normal(jax.random.PRNGKey(1), (128, 16))
        y_ep, aux = jax.jit(lambda p, x: moe_apply_ep(
            p, x, a, train=False, ctx=ctx))(params, x)
        y_ref, _ = moe_apply(params, x, a, train=False)
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-5)
        print("EP_OK")
    """)
    assert "EP_OK" in out


def test_gspmd_moe_sharded_matches_single_device():
    out = _run("""
        from repro.common import param as pm
        from repro.core.moe import MoEArgs, moe_defs, moe_apply
        from repro.sharding import context
        from jax.sharding import PartitionSpec as P, NamedSharding
        mesh = context.make_mesh((2, 4), ("data", "model"))
        ctx = context.MeshContext.for_mesh(mesh, "dp_tp_ep")
        a = MoEArgs(n_experts=8, k=2, d_model=16, d_ff=32,
                    dtype=jnp.float32, capacity_factor=8.0,
                    eval_capacity_factor=8.0)
        params = pm.materialize(moe_defs(a), jax.random.PRNGKey(0))
        params["gate"]["wg"] = 0.5 * jax.random.normal(
            jax.random.PRNGKey(7), params["gate"]["wg"].shape)
        x = jax.random.normal(jax.random.PRNGKey(1), (128, 16))
        y1, _ = moe_apply(params, x, a, train=False)
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        ps = jax.device_put(
            params, NamedSharding(mesh, P()))
        y2, _ = jax.jit(lambda p, x: moe_apply(p, x, a, train=False,
                                               ctx=ctx))(ps, xs)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=2e-4, atol=2e-5)
        print("GSPMD_OK")
    """)
    assert "GSPMD_OK" in out


def test_ep_load_psum_global_batch_semantics():
    """ROADMAP fix: the EP schedule's balancing losses must be computed
    from the *combined-batch* (psum'd) importance/load vectors, not the
    pmean of shard-local CVs — the paper's Eqs. (6)/(11) sum over all
    data-parallel shards.  Construction: every shard routes all of its
    tokens to a different expert pair, so each shard is maximally skewed
    locally while the global load is perfectly balanced; the EP aux loss
    must see the balanced global batch.  Also covers expert_choice, whose
    shard-local load is capacity-uniform by construction (only the psum'd
    global view can ever show imbalance)."""
    out = _run("""
        from repro.common import param as pm
        from repro.core import router as rl
        from repro.core.moe import MoEArgs, moe_defs
        from repro.core.expert_parallel import moe_apply_ep
        from repro.sharding import context
        mesh = context.make_mesh((2, 4), ("data", "model"))
        e, d, t = 8, 16, 128               # 8 shards x 16 tokens
        a = MoEArgs(n_experts=e, k=2, d_model=d, d_ff=32,
                    dtype=jnp.float32, capacity_factor=8.0,
                    eval_capacity_factor=8.0)
        params = pm.materialize(moe_defs(a), jax.random.PRNGKey(0))
        # Gate: feature direction i -> logits peaked at experts (i, i+1).
        wg = np.zeros((d, e), np.float32)
        for i in range(e):
            wg[i, i] = 10.0
            wg[i, (i + 1) % e] = 5.0
        params["gate"]["wg"] = jnp.asarray(wg)
        # Token block s (= shard s under the (data, model) token sharding)
        # points along feature s: the whole shard routes to (s, s+1).
        x = np.zeros((t, d), np.float32)
        for s in range(8):
            x[s * 16:(s + 1) * 16, s] = 4.0
        x += 0.01 * np.random.RandomState(0).randn(t, d)
        x = jnp.asarray(x)
        _, aux = jax.jit(lambda p, x: moe_apply_ep(
            p, x, a, train=False, ctx=context.MeshContext.for_mesh(
                mesh, "dp_tp_ep")))(params, x)
        # Reference: what the old pmean-of-shard-local losses would say.
        router = rl.build(a)
        local = []
        for s in range(8):
            dec = router.route(params, x[s * 16:(s + 1) * 16],
                               train=False)
            local.append(float(dec.aux_loss))
        local_mean = float(np.mean(local))
        global_aux = float(aux["aux_loss"])
        # Each shard is one-expert-pair skewed -> big local CVs; the
        # combined batch is balanced -> the EP loss must be tiny.
        assert local_mean > 0.5, local_mean
        assert global_aux < 0.05, global_aux
        assert global_aux < local_mean / 10.0, (global_aux, local_mean)
        assert float(aux["metrics"]["cv_load"]) < 0.2
        assert abs(float(aux["metrics"]["max_over_mean_load"]) - 1.0) < 0.3
        # expert_choice: shard-local load is capacity-uniform by
        # construction; the psum'd vector is what the metrics report.
        a_ec = MoEArgs(n_experts=e, k=2, d_model=d, d_ff=32,
                       dtype=jnp.float32,
                       router=rl.RouterSpec(policy="expert_choice",
                                            capacity_factor=8.0))
        p_ec = pm.materialize(moe_defs(a_ec), jax.random.PRNGKey(0))
        p_ec["gate"]["wg"] = jnp.asarray(wg)
        _, aux_ec = jax.jit(lambda p, x: moe_apply_ep(
            p, x, a_ec, train=False, ctx=context.MeshContext.for_mesh(
                mesh, "dp_tp_ep")))(p_ec, x)
        assert np.isfinite(float(aux_ec["aux_loss"]))
        assert float(aux_ec["metrics"]["cv_load"]) < 1e-3
        print("EP_GLOBAL_LOAD_OK")
    """)
    assert "EP_GLOBAL_LOAD_OK" in out


def test_elastic_remesh_restore(tmp_path):
    """Checkpoint written under one topology restores under another
    (node-loss scenario: 8 -> 4 devices) with identical values."""
    ckpt = str(tmp_path / "ck")
    out = _run(f"""
        from repro.common import param as pm
        from repro.train.checkpoint import CheckpointManager
        from repro.sharding import context
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = context.make_mesh((4, 2), ("data", "model"))
        tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
        sh = {{"w": NamedSharding(mesh, P("data", "model"))}}
        tree = jax.device_put(tree, sh)
        mgr = CheckpointManager({ckpt!r})
        mgr.save(1, tree)
        print("SAVED")
    """, n_devices=8)
    assert "SAVED" in out
    out = _run(f"""
        from repro.train.checkpoint import CheckpointManager
        from repro.sharding import context
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = context.make_mesh((2, 2), ("data", "model"))
        like = {{"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}}
        sh = {{"w": NamedSharding(mesh, P("model", "data"))}}
        mgr = CheckpointManager({ckpt!r})
        got, extra, step = mgr.restore(1, like, shardings=sh)
        np.testing.assert_array_equal(
            np.asarray(got["w"]),
            np.arange(64, dtype=np.float32).reshape(8, 8))
        assert got["w"].sharding.spec == P("model", "data")
        print("REMESH_OK")
    """, n_devices=4)
    assert "REMESH_OK" in out


def test_ef_compression_sync_multidevice():
    """int8 EF gradient sync over a 2-pod axis: mean within quantization
    error on step one, unbiased accumulated over steps."""
    out = _run("""
        from jax.sharding import PartitionSpec as P
        from repro.sharding import context
        from repro.train.compression import ef_compress_sync, init_ef_state
        mesh = context.make_mesh((2,), ("pod",))
        g = jax.random.normal(jax.random.PRNGKey(0), (2, 64))
        true_mean = jnp.mean(g, axis=0)
        def sync(g, ef):
            return ef_compress_sync({"g": g}, {"g": ef}, "pod")
        fn = context.shard_map(sync, mesh,
                               (P("pod"), P("pod")),
                               ({"g": P("pod")}, {"g": P("pod")}))
        synced, ef = fn(g.reshape(2, 64)[:, :],
                        jnp.zeros((2, 64)))
        got = np.asarray(synced["g"])[0]
        err = np.abs(got - np.asarray(true_mean)).max()
        scale = np.abs(np.asarray(g)).max() / 127
        assert err <= scale + 1e-5, (err, scale)
        print("EF_OK")
    """, n_devices=2)
    assert "EF_OK" in out


@pytest.mark.slow
def test_dryrun_cell_smoke():
    """One real dry-run cell on a 16-device placeholder mesh scaled down."""
    out = _run("""
        from repro.configs import shapes as shp
        from repro.configs.base import get_config
        from repro.launch.steps import lower_cell
        from repro.sharding import context
        mesh = context.make_mesh((4, 4), ("data", "model"))
        cfg = get_config("smollm-135m")
        lowered, spec = lower_cell(cfg, shp.SHAPES["decode_32k"], mesh)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        assert ma.temp_size_in_bytes >= 0
        print("CELL_OK")
    """, n_devices=16)
    assert "CELL_OK" in out


def test_expert_sharded_serving_matches_single_device():
    """The serving shard_map schedule (experts over model, expert width
    over data, every device routing the whole batch) gives the
    single-device layer's output and telemetry, and the transformer picks
    it — and the all-to-all schedule for training — only for the pallas
    backend on a multi-device mesh."""
    out = _run("""
        from repro.common import param as pm
        from repro.core import expert_parallel as ep
        from repro.core.moe import MoEArgs, moe_apply, moe_defs
        from repro.core.router import RouterSpec
        from repro.models import transformer
        from repro.sharding import context
        mesh = context.make_mesh((2, 2), ("data", "model"))
        # capacity 4 per expert for 20 assignments: some are dropped.
        kw = dict(n_experts=4, k=2, d_model=16, d_ff=32,
                  activation="swiglu", dtype=jnp.float32,
                  router=RouterSpec(capacity_factor=0.7,
                                    capacity_multiple=1),
                  kernel_backend="pallas")
        a = MoEArgs(**kw)
        params = pm.materialize(moe_defs(a), jax.random.PRNGKey(0))
        params["gate"]["wg"] = 0.5 * jax.random.normal(
            jax.random.PRNGKey(7), params["gate"]["wg"].shape)
        x = jax.random.normal(jax.random.PRNGKey(1), (12, 16))
        mask = jnp.asarray([1.] * 10 + [0.] * 2)
        want, aux1 = moe_apply(params, x, a, train=False, mask=mask)
        for plan in ("decode_std", "prefill_tp"):
            ctx = context.MeshContext.for_mesh(mesh, plan)
            got, aux = jax.jit(lambda p, x, m: ep.moe_apply_expert_sharded(
                p, x, a, ctx=ctx, mask=m))(params, x, mask)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-6)
            for key in ("expert_load", "overflow"):
                np.testing.assert_array_equal(
                    np.asarray(aux["telemetry"][key]),
                    np.asarray(aux1["telemetry"][key]))
        assert float(aux1["telemetry"]["overflow"].sum()) > 0

        ctx = context.MeshContext.for_mesh(mesh, "dp_tp_ep")
        pick = transformer._moe_schedule
        assert pick(a, ctx, train=True, n_tokens=8) is ep.moe_apply_ep
        assert pick(a, ctx, train=True, n_tokens=6) is moe_apply
        assert pick(a, ctx, train=False, n_tokens=8) not in (
            moe_apply, ep.moe_apply_ep)
        ref = MoEArgs(**dict(kw, kernel_backend="ref"))
        assert pick(ref, ctx, train=True, n_tokens=8) is moe_apply
        assert pick(a, None, train=True, n_tokens=8) is moe_apply
        print("SHARDED_SERVE_OK")
    """, n_devices=4)
    assert "SHARDED_SERVE_OK" in out
