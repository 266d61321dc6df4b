"""The plain float32 reference (bench/reference.py) against the program's
``ref`` kernel backend, at a tiny size on the CPU, so that the chip
comparison starts from a checked oracle.  The program runs here in
float32 too, so the two must agree to float32 rounding: the training
loss with capacity drops, the gradients, and prefill logits.  Every
configuration in ``bench/configs`` is tested, through the architecture
module it names."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import model, reference, weights  # noqa: E402



def _program(name):
    from repro.models import lm
    conf = model.load_config(name)
    m = model.dims(conf, rehearsal=True)
    arch = model.arch(conf)
    cfg = arch.program_config(conf, m, True).replace(
        kernel_backend="ref", param_dtype=jnp.float32,
        compute_dtype=jnp.float32)
    defs = lm.lm_defs(cfg.replace(param_dtype=jnp.bfloat16))
    params = weights.program_params(arch, m, defs, seed=5)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    ref = {k: v.astype(jnp.float32)
           for k, v in weights.make(arch, m, 5).items()}
    return cfg, m, arch, weights.leaf_names(arch, m, defs), params, ref


@pytest.mark.parametrize("name", model.config_names())
def test_loss_and_grads_match_the_program(name):
    from repro.models import lm
    cfg, m, arch, names, params, ref = _program(name)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, m["vocab"], (2, 32)).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens),
             "labels": jnp.asarray(np.roll(tokens, -1, axis=1))}
    (loss_p, met), g_p = jax.value_and_grad(
        lambda p: lm.lm_loss(p, batch, cfg), has_aux=True)(params)
    (loss_r, xent_r), g_r = jax.value_and_grad(
        lambda p: reference.loss_fn(arch, p, batch, m), has_aux=True)(ref)
    assert float(loss_r) == pytest.approx(float(loss_p), abs=1e-5)
    assert float(xent_r) == pytest.approx(float(met["xent"]), abs=1e-5)
    for n, gp in zip(names, jax.tree_util.tree_leaves(g_p)):
        np.testing.assert_allclose(np.asarray(g_r[n]), np.asarray(gp),
                                   rtol=1e-3, atol=1e-6, err_msg=n)


@pytest.mark.parametrize("name", model.config_names())
def test_prefill_logits_match_the_program(name):
    from repro.models import lm, transformer
    from repro.common import param as pm
    cfg, m, arch, _, params, ref = _program(name)
    rng = np.random.RandomState(1)
    seq = rng.randint(1, m["vocab"], 48).astype(np.int32)
    served = []
    for n in (16, 32, 48):
        cache = pm.materialize(transformer.cache_defs(
            cfg.replace(param_dtype=jnp.float32), 1, 64),
            jax.random.PRNGKey(0))
        lg, _ = lm.lm_prefill(params, {"tokens": jnp.asarray(seq[None, :n])},
                              cache, cfg)
        served.append(np.asarray(lg[0]))
    # reference: the prompt is seq[:16]; "served" tokens are the rest, so
    # positions 15, 31 and 47 are among those it scores
    out = reference.served_logits(arch, ref, [(seq[:16], seq[16:48])],
                                  m)[0]
    for n, lg in zip((16, 32, 48), served):
        if n - 16 < out.shape[0]:
            np.testing.assert_allclose(out[n - 16], lg, atol=2e-4)
