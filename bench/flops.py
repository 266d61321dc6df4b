"""Operations and bytes, from the shapes of a configuration (``dims`` of
``bench/configs/<config>.json``), and the peaks of the device they ran on.

Model FLOPs count the multiply-adds a token needs (2 per MAC); the
configuration's architecture module counts one token's forward pass
(``token_flops``), with this module's expert GMM count.  A training
token costs three times its forward pass (forward, and backward for
activations and weights); recomputation is not counted.  Bytes are bf16
(2 a number).
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BYTES = 2


def peaks(device_kind: str) -> dict:
    """The peak table's entry for a device; an unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def train_flops(arch, m: dict, seq_len: int, tokens: int) -> float:
    """Forward and backward FLOPs of ``tokens`` tokens in sequences of
    ``seq_len`` (causal: a token attends (seq_len + 1) / 2 on average),
    each token's forward pass counted by the architecture module
    ``arch`` (``token_flops``)."""
    return 3 * tokens * arch.token_flops(m, (seq_len + 1) / 2)


def gmm_flops(m: dict, rows: float) -> float:
    """The expert SwiGLU FFN on ``rows`` routed rows: w1, w3 and w2."""
    return 2 * 3 * rows * m["d_model"] * m["d_expert"]


def gmm_bytes(m: dict, rows: float, experts: float) -> float:
    """Least bytes: the weights of the experts that got rows, each row
    read once and written once."""
    d, f = m["d_model"], m["d_expert"]
    return BYTES * (3 * d * f * experts + 2 * d * rows)


def dispatch_combine_bytes(m: dict, tokens: float, rows: float) -> float:
    """Dispatch reads the tokens and writes their routed rows; combine
    reads the rows and writes the tokens."""
    return BYTES * m["d_model"] * 2 * (tokens + rows)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
