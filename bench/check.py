"""How ``correct`` is decided: the timed path's output against the plain
reference (``bench/reference.py``), each number beside its limit.

The reference runs the layers of the configuration's architecture
module (``arch``, ``bench/archs/<arch>.py``) on that module's weights.

Serving.  For a sample of finished requests, the reference runs once over
each prompt with its served tokens.  A served (greedy) token's gap is
the amount by which its reference logit lies below the reference's best
at that position; ``mean_logit_gap`` is the mean over every served
position, ``max_logit_gap`` the widest.  The cell's limits name the ones
compared.

Training.  The program's first steps against the reference's, from the
same weights and batches:

* ``loss_gap``: the largest |program - reference| over the steps' losses,
  and ``first_loss_gap`` the first step's alone (both sides from the
  same weights: the forward pass's precision, which later steps' updates
  blur);
* ``grad_norm_gap``: the first gradient as the optimizer received it
  (read back from the program's optimizer state after step 1), per leaf,
  |norm_prog - norm_ref| over max(norm_ref of the leaf, median leaf's);
* ``change_norm_gap``: the same for the norm of each leaf's change over
  the steps.  Leaves whose reference gradient is under a thousandth of
  the median leaf's move by round-off alone and are left out.

Each cell's limits are in its workload file (``check.limits``), set from
the readings of sound runs and of the control as ``PERF.md`` records.
"""
from __future__ import annotations

import numpy as np

from bench import reference, traffic, weights

MOVED = 1e-3      # a leaf below this share of the median gradient is unmoved


def verdict(values: dict, limits: dict) -> dict:
    return {k: {"value": float(v), "limit": limits.get(k)}
            for k, v in values.items()}


def logit_gaps(lg: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Best logit minus the token's logit, per position."""
    return lg.max(-1) - lg[np.arange(len(tokens)), tokens]


def serve_numbers(arch, seqs, m: dict, seed: int, pad_to: int,
                  control: bool = False) -> dict:
    """Gaps of the served tokens under the reference: ``max_logit_gap``,
    the widest, and ``mean_logit_gap``, the mean over every served
    position.  With ``control`` the same two of the tokens that the fp8
    reference puts first at each position (``control_max_gap``,
    ``control_mean_gap``)."""
    if not seqs:
        return {"max_logit_gap": float("inf"),
                "mean_logit_gap": float("inf")}
    params = weights.make(arch, m, seed)
    ref = reference.served_logits(arch, params, seqs, m, pad_to=pad_to)
    gaps = np.concatenate([logit_gaps(lg, t) for lg, (_, t) in zip(ref, seqs)])
    out = {"max_logit_gap": float(gaps.max()),
           "mean_logit_gap": float(gaps.mean())}
    if control:
        low = reference.served_logits(arch, params, seqs, m, control="fp8",
                                      pad_to=pad_to)
        cg = np.concatenate([logit_gaps(r, q.argmax(-1))
                             for r, q in zip(ref, low)])
        out.update(control_max_gap=float(cg.max()),
                   control_mean_gap=float(cg.mean()))
    return out


def batches(m: dict, seed: int, n: int, data: dict) -> list:
    return [traffic.batch_at(m["vocab"], data["seq_len"], data["batch"],
                             data["n_clusters"], data["noise_prob"],
                             traffic.data_seed(seed), step)
            for step in range(n)]


def train_readings(arch, m: dict, seed: int, cell: dict, data: dict,
                   control: str | None = None, half: bool = False) -> dict:
    """The reference's losses, first-gradient norms and change norms over
    the cell's checked steps.  ``half`` is a planted fault: the reference
    trains on the first half of each batch alone."""
    import jax
    import jax.numpy as jnp
    n = cell["check"]["steps"]
    bs = batches(m, seed, n, data)
    if half:
        bs = [{k: v[: max(1, v.shape[0] // 2)] for k, v in b.items()}
              for b in bs]
    bs = [{k: jnp.asarray(v) for k, v in b.items()} for b in bs]
    params = weights.make(arch, m, seed)
    losses, g1, params = reference.train_steps(arch, params, bs, m,
                                               cell["optimizer"], control)
    p0 = weights.make(arch, m, seed)
    diff = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) - b[k].astype(jnp.float32)))) for k in a})
    change = {k: float(v) for k, v in diff(params, p0).items()}
    return {"losses": losses, "grad_norms": g1, "change_norms": change}


def _worst(prog: dict, ref: dict, keep) -> float:
    med = float(np.median([ref[k] for k in ref]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in ref if keep(k))


def train_numbers(prog: dict, ref: dict) -> dict:
    g_med = float(np.median(list(ref["grad_norms"].values())))
    moved = [k for k, v in ref["grad_norms"].items() if v >= MOVED * g_med]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"],
                                                   ref["losses"])),
        "first_loss_gap": abs(prog["losses"][0] - ref["losses"][0]),
        "grad_norm_gap": _worst(prog["grad_norms"], ref["grad_norms"],
                                lambda k: True),
        "change_norm_gap": _worst(prog["change_norms"], ref["change_norms"],
                                  lambda k: k in moved),
    }


def is_correct(checks: dict) -> bool:
    return all(c["limit"] is not None and np.isfinite(c["value"])
               and c["value"] <= c["limit"] for c in checks.values())
