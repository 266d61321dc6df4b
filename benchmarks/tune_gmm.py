"""GMM tiling search: time tile walks per shape, and the tile rule beside
the best of them.

    python benchmarks/tune_gmm.py                      # the f32 table shapes
    python benchmarks/tune_gmm.py --dtype bfloat16 \\
        --shape 16x512x7168x2048 --shape 16x32x7168x2048 --out /tmp/t.json

For each (E, C, K, N) shape it times the static 128^3 walk, the tiles the
rule gives (``gmm.rule_tiles``, what ``plan_blocks`` resolves when the
table has no entry) and a search around them, then prints the rule's time
beside the best searched time and writes the best tiles as a table
(exact (E, C, K, N, dtype) keys, docs/kernels.md §Tiling autotune).

The search is every combination of the rule's own candidate edges
(``gmm.tile_edges``) whose modelled time (``gmm.plan_seconds``) lies
within ``WIDTH`` of the rule's, spread evenly over that ranking, up to
``CAP`` of them.  Candidates are not filtered by the rule's VMEM
estimate: the compiler accepts or refuses each one at the kernel's
budget, and a refusal is reported as such.  The default shapes are the
f32 ones the committed table holds; ``make tune-kernels`` rewrites it.

Each tile gets the wall clock around blocking calls, best of ``--iters``.
On a TPU it also gets its device time, which ranks the tiles: all of a
shape's compiled tiles run ``--iters`` calls each under one profiler
trace, each tile inside a host span of its own, and a tile's time a call
is the device's busy time within its span (``bench/trace_reduce.py``)
over ``--iters``, free of the host's dispatch cost.  On a CPU the wall
clock ranks them (the Pallas interpreter's times).
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax
import jax.numpy as jnp

from bench import trace_reduce
from benchmarks.common import time_call
from repro.kernels import gmm as gmm_lib
from repro.kernels import ops, platform

# (E, C, K, N) per-shard GMM shapes of the committed table (f32).
SHAPES = [
    # microbench expert FFN (benchmarks/microbench.py: E=32, cap=1024,
    # D=64, FF=128): up / down projections + their dw grad shapes.
    (32, 1024, 64, 128),
    (32, 1024, 128, 64),
    (32, 64, 1024, 128),
    (32, 128, 1024, 64),
    # big-buffer acceptance config (tests/test_kernel_eblock.py: E=64,
    # cap=144, d=512, d_ff=8): fwd + dw shapes for both projections.
    (64, 144, 512, 8),
    (64, 144, 8, 512),
    (64, 512, 144, 8),
    (64, 8, 144, 512),
]


# The search: candidates modelled within WIDTH x the rule's time, at most
# CAP of them a shape.
WIDTH = 2.0
CAP = 16


def search_space(e: int, c: int, k: int, n: int, dtype):
    """Tiles to time for one shape: 128^3, the rule's, and up to ``CAP``
    combinations of the rule's candidate edges modelled within ``WIDTH``
    of the rule's time."""
    limit = platform.DEFAULT_VMEM_LIMIT
    rule = gmm_lib.rule_tiles(e, c, k, n, jnp.dtype(dtype).name, limit)
    bound = WIDTH * gmm_lib.plan_seconds(e, c, k, n, *rule, dtype)
    sub = 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8
    near = sorted(
        (gmm_lib.plan_seconds(e, c, k, n, bm, bn, bk, dtype), (bm, bn, bk))
        for bm in gmm_lib.tile_edges(c, sub)
        for bn in gmm_lib.tile_edges(n, 128)
        for bk in gmm_lib.tile_edges(k, 128))
    near = [t for s, t in near if s <= bound and t != rule]
    if len(near) > CAP:
        near = [near[round(i * (len(near) - 1) / (CAP - 1))]
                for i in range(CAP)]
    default = (gmm_lib.DEFAULT_TILE,) * 3
    return rule, [default, rule] + [t for t in near if t != default]


def device_us(fns: dict, x, w, iters: int) -> dict:
    """{tiles: device microseconds a call}: each tile's ``iters`` calls run
    inside a host span of their own under one profiler trace, and a tile's
    time is the device's busy time within its span over ``iters`` (a tile
    whose span the trace lacks is left out)."""
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for i, fn in enumerate(fns.values()):
                with jax.profiler.TraceAnnotation(f"{trace_reduce.HOST_PREFIX}"
                                                  f"tile.{i}"):
                    for _ in range(iters):
                        jax.block_until_ready(fn(x, w))
        tr = trace_reduce.read(trace_reduce.find_xplane(d))
    ops = [(o.start, o.end) for o in tr.devices[0]
           if o.kind not in trace_reduce.CONTAINERS]
    spans = {name: (a, b) for name, a, b in tr.spans}
    out = {}
    for i, tiles in enumerate(fns):
        if f"{trace_reduce.HOST_PREFIX}tile.{i}" not in spans:
            continue
        a, b = spans[f"{trace_reduce.HOST_PREFIX}tile.{i}"]
        busy = trace_reduce.union([(max(s, a), min(e, b)) for s, e in ops
                                   if e > a and s < b])
        out[tiles] = busy / iters * 1e6
    return out


def tune_shape(e: int, c: int, k: int, n: int, dtype=jnp.float32, *,
               warmup: int = 1, iters: int = 3):
    """Time the search space of one shape.  Returns (rule tiles, {tiles:
    us or None where the compiler refused them}, {tiles: wall us}): the
    first dict holds the times that rank (device time on a TPU)."""
    kx, kw = jax.random.split(jax.random.PRNGKey(e * c + k * n))
    x = jax.random.normal(kx, (e, c, k), dtype)
    w = jax.random.normal(kw, (e, k, n), dtype)
    rule, cands = search_space(e, c, k, n, dtype)
    wall: dict[tuple[int, int, int], float | None] = {}
    fns = {}
    for cand in cands:
        bp = gmm_lib.plan_blocks(e, c, k, n, dtype, bm=cand[0], bn=cand[1],
                                 bk=cand[2])
        tiles = (bp.bm, bp.bn, bp.bk)
        if tiles in wall:
            continue
        fn = jax.jit(lambda x_, w_, t=tiles: ops.gmm(x_, w_, bm=t[0],
                                                     bn=t[1], bk=t[2]))
        try:
            wall[tiles] = time_call(fn, x, w, warmup=warmup, iters=iters,
                                    reduce="best")
            fns[tiles] = fn
        except Exception as err:  # noqa: BLE001 — a refused tile is a result
            if "vmem" not in str(err).lower():
                raise
            wall[tiles] = None
    dev = (device_us(fns, x, w, iters) if jax.default_backend() == "tpu"
           else {})
    ranked = {t: dev.get(t, us) for t, us in wall.items()}
    for tiles, us in ranked.items():
        grid = gmm_lib.plan_blocks(e, c, k, n, dtype, bm=tiles[0],
                                   bn=tiles[1], bk=tiles[2]).grid
        model = gmm_lib.plan_seconds(e, c, k, n, *tiles, dtype) * 1e3
        shown = ("refused (VMEM)" if us is None
                 else f"{us / 1e3:.3f} ms (wall {wall[tiles] / 1e3:.3f})")
        print(f"  {e}x{c}x{k}x{n}: tiles={tiles} grid={grid} {shown} "
              f"(model {model:.3f} ms)", flush=True)
    return rule, ranked, wall


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append", default=None,
                    help="ExCxKxN (repeatable; default: the table shapes)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--out", default=None,
                    help="table path (default: the path plan_blocks reads "
                         "— src/repro/kernels/gmm_tunings.json or "
                         "$REPRO_GMM_TUNINGS)")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args()

    dtype = jnp.dtype(args.dtype)
    shapes = ([tuple(int(v) for v in s.split("x")) for s in args.shape]
              if args.shape else SHAPES)
    out_path = args.out or gmm_lib.tunings_path()
    table: dict = {
        "_meta": {
            "tuner": "benchmarks/tune_gmm.py",
            "backend": jax.default_backend(),
            "interpret": jax.default_backend() != "tpu",
            "date": time.strftime("%Y-%m-%d"),
            "reduce": (f"device time, mean of {args.iters}"
                       if jax.default_backend() == "tpu"
                       else f"best-of-{args.iters}"),
        },
    }
    summary = []
    for (e, c, k, n) in shapes:
        print(f"tuning {e}x{c}x{k}x{n}x{dtype.name} ...", flush=True)
        rule, timings, wall = tune_shape(e, c, k, n, dtype,
                                         iters=args.iters)
        ran = {t: us for t, us in timings.items() if us is not None}
        best = min(ran, key=ran.get)
        default = gmm_lib.plan_blocks(e, c, k, n, dtype, bm=128, bn=128,
                                      bk=128)[4:7]
        table[gmm_lib.tuning_key(e, c, k, n, dtype)] = list(best)
        ms = {t: None if us is None else us / 1e3
              for t, us in timings.items()}
        row = {"shape": [e, c, k, n], "dtype": dtype.name,
               "rule": list(rule), "rule_ms": ms[rule],
               "best": list(best), "best_ms": ms[best],
               "default_ms": ms[default], "searched": len(timings),
               "rule_wall_ms": None if wall[rule] is None
               else wall[rule] / 1e3,
               "best_wall_ms": wall[best] / 1e3,
               "rule_over_best": (None if ms[rule] is None
                                  else ms[rule] / ms[best])}
        summary.append(row)
        print(f"  -> rule {list(rule)} {ms[rule]} ms, best {list(best)} "
              f"{ms[best]} ms, 128^3 {ms[default]} ms", flush=True)
    with open(out_path, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
        f.write("\n")
    gmm_lib.invalidate_tunings()
    print(f"wrote {out_path} ({len(table) - 1} shapes)")
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "shapes": summary}))


if __name__ == "__main__":
    main()
