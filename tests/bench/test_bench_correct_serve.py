"""``correct`` for each serve cell of ``BENCHMARK.json``, at the rehearsal
size on the CPU: a sound run passes; a token altered where the engine
produces it, and the control (the fp8 reference's own first choices),
fail.  The harness's look for a chip is skipped (``rehearsal=True``);
the rest of the run is the command's own."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

CELLS = [w["name"] for w in run.benchmark()["workloads"]
         if run.workload(w["name"])["kind"] == "serve"]
SEED = 2 ** 31 + 78


def _run(cell):
    return run.run_cell(cell, SEED, 1.0, False, rehearsal=True,
                        t_start=time.perf_counter())


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    expect = {mt["name"]
              for mt in run.metrics_for(run.benchmark()["end_to_end"], cell)}
    assert "setup_s" in expect and len(expect) > 1
    assert set(r["metrics"]) == expect


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_is_caught(cell, monkeypatch):
    from repro.serve import engine as engine_lib
    real = engine_lib.ServeEngine._sample_rows

    def altered(self, logits, reqs):
        toks = np.asarray(real(self, logits, reqs))
        return (toks + 1) % self.cfg.vocab_size
    monkeypatch.setattr(engine_lib.ServeEngine, "_sample_rows", altered)
    assert not _run(cell)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_fp8_control_fails_the_limit(cell):
    # the control at each position of the sound run's own served sequences
    r = run.run_cell(cell, SEED, 1.0, False, rehearsal=True,
                     t_start=time.perf_counter(), control=True)
    got = r["_detail"]["control"]
    for k, c in r["checks"].items():
        assert got[f"control_{k.replace('_logit', '')}"] > c["limit"], got
