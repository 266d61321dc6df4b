#!/usr/bin/env python3
"""Find a serve cell's knee: the highest open-loop rate whose backlog
does not grow over the window.  Run once when a cell is defined, on the
chip; the rate the cell offers is then written into its workload file.

    python3 bench/sweep.py --workload <cell> --rates 0.5,0.7,0.9 \\
        --seconds 30 --seed 1

Each rate runs the cell's traffic at that rate (its pool sized to the
ramp and the window) in this process and prints one JSON line: the
end-to-end metrics, the requests submitted and due, and the queue and
the occupied slots when the window opened and when it closed.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def pool_size(rate: float, ramp_s: float, seconds: float) -> int:
    """The ramp's requests and as many as fall due in the window."""
    return int(round(rate * ramp_s)) + math.floor(rate * seconds)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("no TPU found: the sweep runs on the chip only")
        return 1
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    ramp = run.workload(args.workload)["traffic"]["arrivals"].get(
        "ramp_s", 0.0)
    for rate in (float(r) for r in args.rates.split(",")):
        over = {"traffic": {"arrivals": {"rate": rate},
                            "pool": pool_size(rate, ramp, args.seconds)},
                "check": {"requests": 2}}
        r = run.run_cell(args.workload, args.seed, args.seconds, False,
                         t_start=time.perf_counter(), overrides=over)
        med = r["_detail"]["medians"]
        print(json.dumps({"rate": rate,
                          "metrics": {k: v["value"] for k, v in
                                      r["metrics"].items()},
                          "medians": med}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
