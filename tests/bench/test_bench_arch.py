"""Each configuration's architecture module (``bench/archs/<arch>.py``,
named by the file's ``arch``) exists, exports what the harness reads of
it, and names every program parameter at both the run's sizes and the
rehearsal's: each program leaf is one of its leaves, of the same shape
and dtype, and each of its leaves is used.  A configuration added later
is tested here without an edit."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import model, weights  # noqa: E402

EXPORTS = ("shapes", "PROGRAM_PATHS", "program_config", "layer",
           "token_flops", "moe_layers")


@pytest.mark.parametrize("name", model.config_names())
def test_arch_contract(name):
    from repro.models import lm
    conf = model.load_config(name)
    arch = model.arch(conf)
    missing = [e for e in EXPORTS if not hasattr(arch, e)]
    assert not missing, f"{conf['arch']} lacks {missing}"
    for rehearsal in (False, True):
        m = model.dims(conf, rehearsal)
        defs = lm.lm_defs(arch.program_config(conf, m, rehearsal))
        names = weights.leaf_names(arch, m, defs)
        assert sorted(names) == sorted(arch.shapes(m)), rehearsal
        assert 0 < arch.moe_layers(m) <= m["n_layers"]
