"""The yardstick against its values at commit 7b1071c, recorded there on
the CPU, before each configuration named its architecture module: the
seed's weights (the sum and the sum of squares of every leaf, seed 5, at
the rehearsal sizes), the reference's loss and served logits for a fixed
sequence at those sizes, and the FLOPs of one token at the run's sizes.
Each must match exactly: moving code between the harness and an
architecture module changes no number it reads."""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import model, reference, weights  # noqa: E402

PIN = {
    "arctic-e8": {
        "leaves": {
            "embed": (-139.51283645629883, 8152.634086197111),
            "ln1": (64.0, 64.0),
            "ln2": (64.0, 64.0),
            "ln_f": (64.0, 64.0),
            "mlp_w1": (8.090438723564148, 66.39419973801024),
            "mlp_w2": (2.8162918090820312, 62.646268214858424),
            "mlp_w3": (-9.452927589416504, 64.52225688465342),
            "unembed": (-0.11033082008361816, 127.44720039272516),
            "w1": (12.038564205169678, 132.19297415122503),
            "w2": (-8.384077310562134, 255.57799931284654),
            "w3": (14.977684736251831, 127.01003615170936),
            "wg": (1.6111931159393862, 3.987926099540988),
            "wk": (-6.245850563049316, 31.879171489341843),
            "wo": (13.176827549934387, 64.03810573782978),
            "wq": (2.342895030975342, 65.19864640711353),
            "wv": (-8.3858402967453, 30.85530483849699),
        },
        "loss": (5.229341983795166, 5.21980619430542),
        "logits": (47.170311061898246, 3922.6882923920457),
        "token_flops": {1.0: 1420439552, 1000.0: 1449082880,
                        2048.5: 1479145472.0},
    },
    "kimi-k2-gqa-e16": {
        "leaves": {
            "embed": (-139.51283645629883, 8152.634086197111),
            "ln1": (128.0, 128.0),
            "ln2": (128.0, 128.0),
            "ln_f": (64.0, 64.0),
            "unembed": (-0.11033082008361816, 127.44720039272516),
            "w1": (25.170923590660095, 261.2547991056797),
            "w2": (-0.9279163479804993, 521.8689510184893),
            "w3": (13.032103270292282, 257.1575986686803),
            "wg": (2.2265495324973017, 8.170912599360973),
            "wk": (1.12766432762146, 65.2026472720392),
            "wo": (12.80200731754303, 128.97241047389804),
            "wq": (-6.8174943923950195, 127.71568003495167),
            "wv": (-6.596814274787903, 63.99269928076667),
        },
        "loss": (5.2992329597473145, 5.2800726890563965),
        "logits": (44.58894163585501, 4014.527303286363),
        "token_flops": {1.0: 4170186752, 1000.0: 4301127680,
                        2048.5: 4438556672.0},
    },
}


def _sums(a):
    a = np.asarray(a, np.float64)
    return float(a.sum()), float((a * a).sum())


@pytest.mark.parametrize("name", sorted(PIN))
def test_weights_reference_and_flops_match_the_parent(name):
    pin = PIN[name]
    conf = model.load_config(name)
    arch = model.arch(conf)
    m = model.dims(conf, rehearsal=True)
    w = weights.make(arch, m, 5)
    assert {k: _sums(v.astype(jnp.float32)) for k, v in w.items()} == \
        pin["leaves"]
    tokens = np.random.RandomState(0).randint(
        0, m["vocab"], (2, 32)).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens),
             "labels": jnp.asarray(np.roll(tokens, -1, axis=1))}
    loss, xent = reference.loss_fn(arch, w, batch, m)
    assert (float(loss), float(xent)) == pin["loss"]
    seq = np.random.RandomState(1).randint(
        1, m["vocab"], 48).astype(np.int32)
    lg = reference.served_logits(arch, w, [(seq[:16], seq[16:48])], m)[0]
    assert _sums(lg) == pin["logits"]
    full = model.dims(conf)
    assert {c: arch.token_flops(full, c) for c in pin["token_flops"]} == \
        pin["token_flops"]
