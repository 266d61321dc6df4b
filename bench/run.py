#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its
settings in ``bench/workloads/<cell>.json``, its model configuration in
``bench/configs/<config>.json``, the architecture that file names in
``bench/archs/<arch>.py``, and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the first seconds of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), then ``checks``: each number compared for ``correct``
beside its limit, which the last lines of standard error repeat.  With
no TPU, or fewer chips than the cell asks for, it prints no result and
exits 1.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(name: str) -> dict:
    with open(os.path.join(ROOT, "bench", "workloads", f"{name}.json")) as f:
        return json.load(f)


def metrics_for(metrics: list[dict], cell: str) -> list[dict]:
    """The entries of ``metrics`` that the cell reports: those without a
    ``workloads`` list, and those whose list names it."""
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def reader(metric: str):
    """The module ``bench/metrics/<metric>.py`` (names hold dots)."""
    path = os.path.join(ROOT, "bench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reduce_layers(bench: dict, name: str, ctx: dict) -> dict:
    """Each per-layer metric of the cell from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in metrics_for(bench["per_layer"], name):
        value = reader(metric["name"]).read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys laid over it, nested dicts merged."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (merged(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             rehearsal: bool = False, t_start: float | None = None,
             trace_dir: str | None = None, control: bool = False,
             overrides: dict | None = None) -> dict:
    """Run the cell in this process and return the result object.
    ``rehearsal`` runs it at the configuration's tiny sizes on whatever
    device JAX has (the CPU tests); the command line never sets it.
    ``control`` (``bench/calibrate.py``) also reads the control;
    ``overrides`` (``bench/sweep.py``) is laid over the workload file."""
    import jax

    from bench import flops, model, serve, train, trace_reduce
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = merged(workload(name), overrides or {})
    if rehearsal:
        cell["check"] = dict(cell["check"],
                             **cell["rehearsal"].get("check", {}))
    conf = model.load_config(entry["config"])
    m = model.dims(conf, rehearsal)
    arch = model.arch(conf)
    devices = jax.devices()[: entry["chips"]]
    kind = devices[0].device_kind
    # a rehearsal's numbers only exercise the arithmetic: v5e peaks
    peak = flops.peaks("TPU v5 lite" if rehearsal else kind)
    own_dir = trace and trace_dir is None
    if own_dir:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    runner = {"serve": serve, "train": train}[cell["kind"]]
    extra = {"control": True} if control else {}
    t0 = T_START if t_start is None else t_start
    try:
        out = runner.run(cell, conf, m, seed, seconds,
                         trace_dir if trace else None, t0, rehearsal,
                         **extra)
        tr = (trace_reduce.read(trace_reduce.find_xplane(trace_dir))
              if trace else None)
    finally:
        if own_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    from bench import check
    correct = (check.is_correct(out["checks"]) and out["failed"] == 0
               and out["attempted"] > 0)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    if trace:
        ctx = {"cell": cell, "m": m, "arch": arch, "peak": peak,
               "trace": tr, "run": out, "chips": len(devices)}
        metrics = reduce_layers(bench, name, ctx)
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
    else:
        metrics = {mt["name"]: {"value": float(out["metrics"][mt["name"]]),
                                "unit": mt["unit"]}
                   for mt in metrics_for(bench["end_to_end"], name)}
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = trace_reduce.breakdown(tr)
    result["checks"] = out["checks"]
    result["_detail"] = {k: out.get(k) for k in ("medians", "traced",
                                                 "fallbacks", "control",
                                                 "reference_s")}
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    entry = next((w for w in benchmark()["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    import jax
    devices = jax.devices()
    log(f"devices: {len(devices)} x {devices[0].platform} "
        f"{devices[0].device_kind}")
    if devices[0].platform != "tpu":
        log("no TPU found: the benchmark runs on the chip only")
        return 1
    if len(devices) < entry["chips"]:
        log(f"{args.workload} needs {entry['chips']} chips, found "
            f"{len(devices)}")
        return 1
    from repro.common.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    detail = result.pop("_detail")
    log(f"detail: {json.dumps(detail)}")
    for k, c in result["checks"].items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
