"""Train cells: the program's ``Trainer`` step on its own data iterator.

Set-up makes the weights on the device, builds the ``Trainer`` (its
jitted step and optimizer state) and drives that same object through the
cell's checked steps, which compile the step.  Those steps' losses, the
first gradient norms (read back from the optimizer state after step 1)
and each leaf's change are kept for the check.  The window then runs
steps on the same object until ``--seconds`` have passed, one step
dispatched ahead of the one whose loss it waits for.  ``train_tok_s`` is
the tokens of every step sent in the window over the time from its start
to the end of its last step.  Checkpointing stays off.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import shutil
import tempfile
import time

AHEAD = 1          # steps dispatched ahead of the one waited on


def opt_grad_norms(opt, names):
    """Per-leaf norm of the first gradient as the factored optimizer got
    it (clipped), from its state after one step: a factored leaf keeps
    vr = (1 - b2) mean(g^2, -1), an elementwise one v = (1 - b2) g^2."""
    import jax
    import jax.numpy as jnp
    from repro.optim.optimizers import OptConfig
    b2 = OptConfig().b2
    leaves = jax.tree_util.tree_leaves(
        opt["mu"], is_leaf=lambda x: isinstance(x, dict) and (
            "vr" in x or "v" in x))

    def norm(s, p_last):
        if "vr" in s:
            return jnp.sqrt(jnp.sum(s["vr"]) * p_last / (1 - b2))
        return jnp.sqrt(jnp.sum(s["v"]) / (1 - b2))

    lasts = [s["vc"].shape[-1] if "vr" in s else 1 for s in leaves]
    vals = jax.jit(lambda ls: [norm(s, n) for s, n in zip(ls, lasts)])(
        leaves)
    return {n: float(v) for n, v in zip(names, vals)}


def change_norms(params, p0, names):
    import jax
    import jax.numpy as jnp
    vals = jax.jit(lambda a, b: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])(params, p0)
    return {n: float(v) for n, v in zip(names, vals)}


def run(cell: dict, conf: dict, m: dict, seed: int, seconds: float,
        trace_dir: str | None, t_start: float, rehearsal: bool,
        control: bool = False) -> dict:
    import jax

    from bench import check, model, traffic, weights
    from repro.data.pipeline import DataConfig, DataIterator
    from repro.kernels import backend as backend_lib
    from repro.models import lm
    from repro.optim.optimizers import OptConfig
    from repro.sharding import context as ctx_lib
    from repro.train.trainer import Trainer, TrainLoopConfig

    clock = time.perf_counter
    data = dict(cell["data"])
    if rehearsal:
        data.update(cell["rehearsal"].get("data", {}))
    arch = model.arch(conf)
    cfg = arch.program_config(conf, m, rehearsal)
    defs = lm.lm_defs(cfg)
    names = weights.leaf_names(arch, m, defs)
    params = weights.program_params(arch, m, defs, seed)
    opt = cell["optimizer"]
    workdir = tempfile.mkdtemp(prefix="bench-train-")
    trainer = Trainer(
        loss_fn=lambda p, b, r: lm.lm_loss(p, b, cfg, rng=r),
        params=params,
        oc=OptConfig(kind=opt["kind"], learning_rate=opt["learning_rate"],
                     warmup_steps=opt["warmup_steps"]),
        loop=TrainLoopConfig(total_steps=10 ** 9,
                             checkpoint_every=10 ** 9),
        data_iter=DataIterator(DataConfig(
            vocab_size=m["vocab"], seq_len=data["seq_len"],
            batch_size=data["batch"], n_clusters=data["n_clusters"],
            noise_prob=data["noise_prob"], seed=traffic.data_seed(seed))),
        workdir=workdir)
    del params
    rng = jax.random.PRNGKey(trainer.loop.seed)
    count = [0]

    def span(name):
        return (jax.profiler.TraceAnnotation(name) if trace_dir
                else contextlib.nullcontext())

    def launch():
        """Dispatch one step of the trainer's own call on its own feed."""
        with span("bench.data"):
            batch = next(trainer.data_iter)
        with span("bench.step"), ctx_lib.MeshContext.null():
            trainer.state, metrics = trainer.step_fn(
                trainer.state, batch, jax.random.fold_in(rng, count[0]))
        count[0] += 1
        return metrics

    def wait(metrics):
        with span("bench.wait"):
            jax.block_until_ready(metrics["loss"])
        return metrics

    losses = []
    for i in range(cell["check"]["steps"]):
        met = wait(launch())
        losses.append(float(met["loss"]))
        if i == 0:
            g1 = opt_grad_norms(trainer.state["opt"], names)
    p0 = weights.program_params(arch, m, defs, seed)
    moved = change_norms(trainer.state["params"], p0, names)
    del p0
    fallbacks = sum(backend_lib.fallbacks().values())
    tokens_per_step = data["batch"] * data["seq_len"]

    trace_s = min(seconds, 6.0)
    traced = None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t0 = clock()
    t_end = t0 + seconds
    window = span("bench.window")
    window.__enter__()
    tracing = bool(trace_dir)
    steps, dropped = 0, []
    # One step runs ahead of the one waited on, so that the chip has work
    # while the host stands still.  When the time is up nothing more is
    # sent; every step sent is waited for and counts, over all that time.
    inflight = collections.deque()
    t_last = t0
    while True:
        if clock() < t_end:
            inflight.append(launch())
            if len(inflight) <= AHEAD:
                continue
        if not inflight:
            break
        met = wait(inflight.popleft())
        t_last = clock()
        steps += 1
        dropped.append(float(met.get("fraction_dropped", 0.0)))
        if tracing and t_last >= t0 + trace_s:
            window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
            traced = {"seconds": t_last - t0, "steps": steps,
                      "tokens": steps * tokens_per_step,
                      "fraction_dropped": sum(dropped) / len(dropped)}
    if tracing:
        window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        traced = {"seconds": t_last - t0, "steps": steps,
                  "tokens": steps * tokens_per_step,
                  "fraction_dropped": sum(dropped) / len(dropped)}
    from bench.serve import peak_bytes
    out = {
        "attempted": steps, "failed": 0,
        "metrics": {"train_tok_s": steps * tokens_per_step / (t_last - t0),
                    "setup_s": t0 - t_start},
        "medians": {"steps": steps, "window_s": t_last - t0,
                    "losses": losses},
        "memory_peak_bytes": peak_bytes(),
        "traced": traced,
        "fallbacks": fallbacks,
        "seq_len": data["seq_len"],
    }
    del trainer
    gc.collect()
    shutil.rmtree(workdir, ignore_errors=True)
    prog = {"losses": losses, "grad_norms": g1, "change_norms": moved}
    t_ref = clock()
    ref = check.train_readings(arch, m, seed, cell, data)
    out["reference_s"] = clock() - t_ref
    out["checks"] = check.verdict(check.train_numbers(prog, ref),
                                  cell["check"]["limits"])
    if control:
        out["control"] = {
            "fp8": check.train_numbers(check.train_readings(
                arch, m, seed, cell, data, control="fp8_train"), ref),
            "half_batch": check.train_numbers(check.train_readings(
                arch, m, seed, cell, data, half=True), ref)}
    return out
