#!/usr/bin/env python3
"""Readings that set a cell's limits: the numbers ``correct`` compares,
from sound runs of the program on many seeds, and from the control and
the planted faults on a few, at the cell's own size, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 10 [--control 1]

The benchmark's own runs never run this.  Each seed prints one JSON line
with the compared numbers (``checks``) and, with ``--control 1``, the
control's (serving: the fp8 reference's own first choices at each
position of the served sequences; training: the fp8 reference's steps
and the reference trained on half of each batch, against the float32
reference).  ``PERF.md`` records the readings and the limits set from
them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("no TPU found: calibration runs on the chip only")
        return 1
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run.run_cell(args.workload, seed, args.seconds, False,
                         t_start=time.perf_counter(),
                         control=bool(args.control))
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "checks": {k: v["value"]
                                     for k, v in r["checks"].items()},
                          "control": r["_detail"].get("control"),
                          "metrics": {k: v["value"] for k, v in
                                      r["metrics"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
