"""``correct`` for each train cell of ``BENCHMARK.json``, at the rehearsal
size on the CPU: a sound run passes; the control (the reference in fp8
in the program's place) and each fault planted under the timed path
fail.  The harness's look for a chip is skipped (``rehearsal=True``);
the rest of the run is the command's own."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import check, model, run  # noqa: E402

CELLS = [w for w in run.benchmark()["workloads"]
         if run.workload(w["name"])["kind"] == "train"]
IDS = [w["name"] for w in CELLS]
SEED = 2 ** 31 + 77


def _run(cell):
    return run.run_cell(cell, SEED, 0.5, False, rehearsal=True,
                        t_start=time.perf_counter())


@pytest.mark.parametrize("cell", IDS)
def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    expect = {mt["name"]
              for mt in run.metrics_for(run.benchmark()["end_to_end"], cell)}
    assert "setup_s" in expect and len(expect) > 1
    assert set(r["metrics"]) == expect
    assert list(r)[-2:] == ["checks", "_detail"]


@pytest.mark.parametrize("cell", IDS)
def test_state_left_unchanged_is_caught(cell, monkeypatch):
    from repro.train import trainer as trainer_lib
    real = trainer_lib.make_train_step

    def frozen(*a, **kw):
        step = real(*a, **kw)

        def same(state, batch, rng):
            _, metrics = step(state, batch, rng)
            return state, metrics
        return same
    monkeypatch.setattr(trainer_lib, "make_train_step", frozen)
    r = _run(cell)
    assert not r["correct"]
    assert r["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", IDS)
def test_half_batch_is_caught(cell, monkeypatch):
    from repro.models import lm
    real = lm.lm_loss

    def half(params, batch, cfg, **kw):
        b = batch["tokens"].shape[0] // 2
        return real(params, {k: v[:b] for k, v in batch.items()}, cfg, **kw)
    monkeypatch.setattr(lm, "lm_loss", half)
    assert not _run(cell)["correct"]


@pytest.mark.parametrize("entry", CELLS, ids=IDS)
def test_fp8_control_fails_the_limits(entry):
    cell = run.workload(entry["name"])
    cell["check"] = dict(cell["check"], **cell["rehearsal"]["check"])
    conf = model.load_config(entry["config"])
    m = model.dims(conf, rehearsal=True)
    data = dict(cell["data"], **cell["rehearsal"]["data"])
    arch = model.arch(conf)
    ref = check.train_readings(arch, m, SEED, cell, data)
    low = check.train_readings(arch, m, SEED, cell, data, control="fp8_train")
    verdict = check.verdict(check.train_numbers(low, ref),
                             cell["check"]["limits"])
    assert not check.is_correct(verdict), verdict
