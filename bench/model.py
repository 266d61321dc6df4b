"""A configuration file -> the sizes the benchmark runs and the program's
``ModelConfig`` at those sizes.

``dims`` in ``bench/configs/<config>.json`` are the sizes as run; the
published keys beside them say where they come from.  The program's
config is its registry entry (``program_arch``) with those sizes, the
Pallas kernels, and the router the file states (deterministic top-k at
the stated capacity factor).  ``rehearsal=True`` takes the file's tiny
``rehearsal_dims`` instead, for the CPU tests.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_config(name: str) -> dict:
    path = os.path.join(HERE, "configs", f"{name}.json")
    with open(path) as f:
        return json.load(f)


def dims(conf: dict, rehearsal: bool = False) -> dict:
    return dict(conf["rehearsal_dims"] if rehearsal else conf["dims"])


def program_config(conf: dict, m: dict, *, rehearsal: bool = False):
    """The program's ModelConfig at the sizes ``m``."""
    from repro.configs.base import get_config
    from repro.core.router import RouterSpec

    cfg = get_config(conf["program_arch"]).replace(
        n_layers=m["n_layers"], d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"],
        n_experts=m["n_experts"], moe_k=m["k"], moe_d_ff=m["d_expert"],
        d_ff=m["d_dense"], vocab_size=m["vocab"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["eps"]),
        kernel_backend="pallas",
        router=RouterSpec(capacity_factor=float(m["capacity_factor"]),
                          noise=False))
    if rehearsal:
        cfg = cfg.replace(q_block=16, kv_block=16)
    if bool(cfg.dense_residual) != bool(m["d_dense"]):
        raise ValueError(f"{conf['name']}: dense residual FFN in the program "
                         f"({cfg.dense_residual}) and d_dense={m['d_dense']} "
                         "disagree")
    return cfg
