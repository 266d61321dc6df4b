"""``correct`` for the serve cell, at the rehearsal size on the CPU: a
sound run passes; a token altered where the engine produces it, and the
control (the fp8 reference's own first choices), fail.  The harness's
look for a chip is skipped (``rehearsal=True``); the rest of the run is
the command's own."""
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

CELL = "kimi-e16.chat-poisson"
SEED = 2 ** 31 + 78


def _run():
    return run.run_cell(CELL, SEED, 1.0, False, rehearsal=True,
                        t_start=time.perf_counter())


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"itl_p95_ms", "setup_s"}


def test_altered_token_is_caught(monkeypatch):
    from repro.serve import engine as engine_lib
    real = engine_lib.ServeEngine._sample_rows

    def altered(self, logits, reqs):
        toks = np.asarray(real(self, logits, reqs))
        return (toks + 1) % self.cfg.vocab_size
    monkeypatch.setattr(engine_lib.ServeEngine, "_sample_rows", altered)
    assert not _run()["correct"]


def test_fp8_control_fails_the_limit():
    # the control at each position of the sound run's own served sequences
    r = run.run_cell(CELL, SEED, 1.0, False, rehearsal=True,
                     t_start=time.perf_counter(), control=True)
    got = r["_detail"]["control"]
    for k, c in r["checks"].items():
        assert got[f"control_{k.replace('_logit', '')}"] > c["limit"], got
