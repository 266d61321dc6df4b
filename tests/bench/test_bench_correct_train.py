"""``correct`` for the train cell, at the rehearsal size on the CPU: a
sound run passes; the control (the reference in fp8 in the program's
place) and each fault planted under the timed path fail.  The harness's
look for a chip is skipped (``rehearsal=True``); the rest of the run is
the command's own."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import check, model, run  # noqa: E402

CELL = "arctic-e8.train-4k"
SEED = 2 ** 31 + 77


def _run():
    return run.run_cell(CELL, SEED, 0.5, False, rehearsal=True,
                        t_start=time.perf_counter())


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tok_s", "setup_s"}
    assert list(r)[-2:] == ["checks", "_detail"]


def test_state_left_unchanged_is_caught(monkeypatch):
    from repro.train import trainer as trainer_lib
    real = trainer_lib.make_train_step

    def frozen(*a, **kw):
        step = real(*a, **kw)

        def same(state, batch, rng):
            _, metrics = step(state, batch, rng)
            return state, metrics
        return same
    monkeypatch.setattr(trainer_lib, "make_train_step", frozen)
    r = _run()
    assert not r["correct"]
    assert r["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_caught(monkeypatch):
    from repro.models import lm
    real = lm.lm_loss

    def half(params, batch, cfg, **kw):
        b = batch["tokens"].shape[0] // 2
        return real(params, {k: v[:b] for k, v in batch.items()}, cfg, **kw)
    monkeypatch.setattr(lm, "lm_loss", half)
    assert not _run()["correct"]


def test_fp8_control_fails_the_limits():
    cell = run.workload(CELL)
    cell["check"] = dict(cell["check"], **cell["rehearsal"]["check"])
    m = model.dims(model.load_config("arctic-e8"), rehearsal=True)
    data = dict(cell["data"], **cell["rehearsal"]["data"])
    ref = check.train_readings(m, SEED, cell, data)
    low = check.train_readings(m, SEED, cell, data, control="fp8_train")
    verdict = check.verdict(check.train_numbers(low, ref),
                             cell["check"]["limits"])
    assert not check.is_correct(verdict), verdict
