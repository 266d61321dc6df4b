"""MeshContext subsystem: plan resolution on 1- and 8-device meshes,
Manual-axis stripping, the contextvar plumbing, and the no-abstract-mesh
fallback (identity constraints off-mesh)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.param import ParamDef
from repro.sharding import context, partition

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


# A representative ParamDef tree touching the interesting logical axes.
def _defs():
    return {
        "w1": ParamDef((8, 16, 32), ("experts", "expert_embed",
                                     "expert_mlp"), dtype=jnp.float32),
        "unembed": ParamDef((16, 128), ("embed_fsdp", "vocab"),
                            dtype=jnp.float32),
        "scale": ParamDef((16,), ("embed",), init="ones",
                          dtype=jnp.float32),
    }


# ---------------------------------------------------------------------------
# 1-device mesh: every plan must resolve every ParamDef without error and
# produce valid NamedShardings (everything collapses to replication).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plan", sorted(partition.PLANS))
def test_every_plan_resolves_on_one_device(plan):
    mesh = context.make_mesh((1, 1), ("data", "model"))
    ctx = context.MeshContext.for_mesh(mesh, plan)
    shd = ctx.tree_shardings(_defs())
    for leaf in jax.tree_util.tree_leaves(shd):
        assert isinstance(leaf, jax.sharding.NamedSharding)
    # Constraint inside jit must be a functional no-op on one device.
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    with ctx:
        y = jax.jit(lambda v: context.with_constraint(
            v, ("batch", "embed")))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_divisibility_fallback_recorded():
    """A dim not divisible by its mesh axes falls back (and is recorded),
    never errors."""
    mesh = context.make_mesh((1, 1), ("data", "model"))
    ctx = context.MeshContext.for_mesh(mesh, "dp_tp_ep")
    # 7 is not divisible by anything > 1; on a 1-device mesh axes of size 1
    # always divide, so force the interesting case via an 8-dev subprocess
    # below.  Here just check the fallback list plumbing.
    fallbacks = []
    spec = ctx.resolve((7, 16), ("experts", "expert_embed"), fallbacks)
    assert isinstance(spec, jax.sharding.PartitionSpec)


# ---------------------------------------------------------------------------
# Manual-axis stripping (the pipeline stage-axis path)
# ---------------------------------------------------------------------------

def test_manual_axis_stripped_from_specs():
    mesh = context.make_mesh((1, 1), ("data", "model"))
    ctx = context.MeshContext.for_mesh(mesh, "dp_tp_ep")
    stage_ctx = ctx.manual("data")
    assert stage_ctx.manual_axes == frozenset({"data"})
    assert "data" not in stage_ctx.auto_axes
    # batch resolves to ("pod","data") under dp_tp_ep -> data must be gone.
    spec = stage_ctx.resolve((8, 16), ("batch", "embed"))
    flat = []
    for e in spec:
        if e is None:
            continue
        flat += list(e) if isinstance(e, tuple) else [e]
    assert "data" not in flat
    # the parent context is untouched (frozen dataclass derivation)
    assert ctx.manual_axes == frozenset()


# ---------------------------------------------------------------------------
# contextvar plumbing + the no-abstract-mesh fallback
# ---------------------------------------------------------------------------

def test_null_context_constraint_is_identity():
    x = jnp.ones((4, 4))
    assert context.MeshContext.null().with_constraint(
        x, ("batch", "embed")) is x


def test_no_ctx_no_abstract_mesh_is_identity():
    """With no active context and no ambient abstract mesh the
    free-function constraint must return its input unchanged."""
    assert context.current_ctx() is None
    x = jnp.ones((4, 4))
    y = context.with_constraint(x, ("batch", "embed"))
    if context.abstract_mesh_or_none() is None:
        assert y is x
    else:
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_contextvar_nesting():
    mesh = context.make_mesh((1, 1), ("data", "model"))
    outer = context.MeshContext.for_mesh(mesh, "dp_tp_ep")
    inner = context.MeshContext.for_mesh(mesh, "decode_std")
    assert context.current_ctx() is None
    with outer:
        assert context.current_ctx() is outer
        with inner:
            assert context.current_ctx() is inner
            with inner:       # re-entrant on the same object
                assert context.current_ctx() is inner
            assert context.current_ctx() is inner
        assert context.current_ctx() is outer
    assert context.current_ctx() is None


def test_with_plan_derivation():
    mesh = context.make_mesh((1, 1), ("data", "model"))
    ctx = context.MeshContext.for_mesh(mesh, "dp_tp_ep")
    d = ctx.with_plan("decode_std")
    assert d.rules.name == "decode_std" and ctx.rules.name == "dp_tp_ep"
    assert d.mesh is ctx.mesh


# ---------------------------------------------------------------------------
# 8-device meshes (subprocess): every plan, real shardings, and sharded
# execution equivalence through the MoE layer.
# ---------------------------------------------------------------------------

def test_every_plan_resolves_on_eight_devices():
    out = _run("""
        from repro.common.param import ParamDef
        from repro.sharding import context, partition
        mesh = context.make_mesh((2, 4), ("data", "model"))
        defs = {
            "w1": ParamDef((8, 16, 32), ("experts", "expert_embed",
                                         "expert_mlp"),
                           dtype=jnp.float32),
            "unembed": ParamDef((16, 128), ("embed_fsdp", "vocab"),
                                dtype=jnp.float32),
            "odd": ParamDef((7, 16), ("experts", "expert_embed"),
                            dtype=jnp.float32),
        }
        for plan in sorted(partition.PLANS):
            ctx = context.MeshContext.for_mesh(mesh, plan)
            fallbacks = []
            shd = ctx.tree_shardings(defs, fallbacks)
            for leaf in jax.tree_util.tree_leaves(shd):
                assert isinstance(leaf, jax.sharding.NamedSharding)
            # the 7-dim 'odd' leaf must have fallen back, not failed
        # dp_tp_ep: experts=8 shards over model=4
        ctx = context.MeshContext.for_mesh(mesh, "dp_tp_ep")
        spec = ctx.resolve((8, 16, 32), ("experts", "expert_embed",
                                         "expert_mlp"))
        assert spec[0] == "model", spec
        print("PLANS_OK")
    """)
    assert "PLANS_OK" in out


def test_sharded_constraint_matches_unsharded_execution():
    out = _run("""
        from repro.sharding import context
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = context.make_mesh((2, 4), ("data", "model"))
        ctx = context.MeshContext.for_mesh(mesh, "dp_tp_ep")

        def f(x):
            h = context.with_constraint(x, ("tokens", "embed"))
            return jnp.tanh(h) * 2.0

        x = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
        y_ref = f(x)                      # eager, off-mesh: identity path
        with ctx:
            xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
            y = jax.jit(f)(xs)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-6, atol=1e-6)
        print("CONSTRAIN_OK")
    """)
    assert "CONSTRAIN_OK" in out


def test_manual_stripping_on_eight_devices():
    """shard_map manual over 'data' with an in-body constraint on the
    Auto axis: numerics match the unsharded reference."""
    out = _run("""
        from jax.sharding import PartitionSpec as P
        from repro.sharding import context
        mesh = context.make_mesh((4, 2), ("data", "model"))
        ctx = context.MeshContext.for_mesh(mesh, "dp_tp_ep")
        stage_ctx = ctx.manual("data")

        def body(x):
            h = stage_ctx.with_constraint(x, ("batch", "embed"))
            return h * 3.0

        fn = context.shard_map(body, mesh, (P("data"),), P("data"),
                               manual_axes=("data",))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
        y = jax.jit(fn)(x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x) * 3.0,
                                   rtol=1e-6)
        print("MANUAL_OK")
    """)
    assert "MANUAL_OK" in out
