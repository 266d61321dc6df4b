"""The one traffic generator: a workload file's parameters + a seed ->
requests (serving) or token batches (training).

Serving.  Lengths come from a fixed grid of quantiles of the stated
lognormal (median, sigma, clipped to [min, max]), so every seed serves
the same multiset of sizes; the seed only orders them and draws the
token ids.  The ramp that runs in set-up and the measured window each get
a grid of their own, so the window's multiset is the same for every
seed too.  Arrivals:

* ``poisson``: rate ``rate`` per second, gaps from the quantile grid of
  the exponential, in the seed's order;
* ``gamma``: rate ``rate``, gaps from the quantile grid of a gamma with
  coefficient of variation ``cv``.

Training.  ``batch_at`` is the clustered-bigram token generator of the
program's data pipeline, copied: each sequence follows one cluster's
rule ``next = (mult * prev + add) % vocab`` with a little uniform noise.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass
class Req:
    i: int
    prompt: np.ndarray
    out_len: int
    arrival: float                    # seconds after the traffic starts


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_grid(spec: dict, n: int) -> np.ndarray:
    z = np.array([statistics.NormalDist().inv_cdf(q) for q in _grid(n)])
    v = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.round(v), spec["min"], spec["max"]).astype(np.int64)


def gap_grid(arr: dict, n: int) -> np.ndarray:
    q = _grid(n)
    if arr["process"] == "poisson":
        return -np.log1p(-q) / arr["rate"]
    if arr["process"] == "gamma":
        shape = 1.0 / arr["cv"] ** 2
        from scipy.stats import gamma          # noqa: PLC0415
        return gamma.ppf(q, shape, scale=1.0 / (arr["rate"] * shape))
    raise ValueError(f"no gaps for arrivals {arr['process']!r}")


def requests(traffic: dict, vocab: int, seed: int) -> list[Req]:
    """The pool of requests: the ramp's part, due in its first ``ramp_s``
    seconds (``rate * ramp_s`` of them), then the window's, due from
    ``ramp_s`` on.  Each part has its own quantile grid of prompt and
    output lengths and of gaps, in the seed's order, so that every seed
    sends the window the same multiset of sizes and arrival gaps."""
    n = traffic["pool"]
    arr = traffic["arrivals"]
    ramp_s = arr.get("ramp_s", 0.0)
    n_ramp = min(n, int(round(arr["rate"] * ramp_s)))
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    out: list[Req] = []
    for k, start in ((n_ramp, 0.0), (n - n_ramp, ramp_s)):
        if k == 0:
            continue
        plen = rng.permutation(lognormal_grid(traffic["prompt"], k))
        olen = rng.permutation(lognormal_grid(traffic["output"], k))
        toks = rng.integers(1, vocab, int(plen.sum()), dtype=np.int64)
        starts = np.concatenate([[0], np.cumsum(plen)[:-1]])
        times = (start + np.cumsum(rng.permutation(gap_grid(arr, k)))).tolist()
        out += [Req(len(out) + i, toks[s:s + p].astype(np.int32), int(o),
                    times[i])
                for i, (s, p, o) in enumerate(zip(starts, plen, olen))]
    return out


def data_seed(seed: int) -> int:
    """The program's data pipeline takes a seed below 2**31."""
    return int(seed) % (2 ** 31 - 1)


def _cluster_tables(vocab, n_clusters, seed):
    rng = np.random.RandomState(seed ^ 0x5EED)
    mult = rng.randint(1, vocab, size=n_clusters) | 1
    add = rng.randint(0, vocab, size=n_clusters)
    return mult, add


def batch_at(vocab: int, seq_len: int, batch: int, n_clusters: int,
             noise_prob: float, seed: int, step: int) -> dict:
    """Tokens and labels [batch, seq_len] of one step (numpy int32)."""
    mult, add = _cluster_tables(vocab, n_clusters, seed)
    rng = np.random.RandomState((seed * 1_000_003 + step) % (2 ** 31 - 1))
    clusters = rng.randint(0, n_clusters, size=batch)
    toks = np.zeros((batch, seq_len + 1), np.int64)
    toks[:, 0] = rng.randint(0, vocab, size=batch)
    noise = rng.rand(batch, seq_len) < noise_prob
    rand_tok = rng.randint(0, vocab, size=(batch, seq_len))
    for t in range(seq_len):
        nxt = (toks[:, t] * mult[clusters] + add[clusters]) % vocab
        toks[:, t + 1] = np.where(noise[:, t], rand_tok[:, t], nxt)
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32)}


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) of all values, linear between ranks."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.nan
    return float(np.percentile(v, q))
