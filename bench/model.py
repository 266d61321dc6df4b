"""A configuration file -> the sizes the benchmark runs and the
architecture module that knows their layers.

``dims`` in ``bench/configs/<config>.json`` are the sizes as run; the
published keys beside them say where they come from.  ``rehearsal=True``
takes the file's tiny ``rehearsal_dims`` instead, for the CPU tests.
The file's ``arch`` names the module ``bench/archs/<arch>.py`` that
gives those sizes' weights, the program's config, the reference's
layers and the FLOP counts.
"""
from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def config_names() -> list[str]:
    """Every configuration in ``bench/configs``, by name."""
    return sorted(f[:-len(".json")] for f in os.listdir(
        os.path.join(HERE, "configs")) if f.endswith(".json"))


def load_config(name: str) -> dict:
    path = os.path.join(HERE, "configs", f"{name}.json")
    with open(path) as f:
        return json.load(f)


def dims(conf: dict, rehearsal: bool = False) -> dict:
    return dict(conf["rehearsal_dims"] if rehearsal else conf["dims"])


def arch(conf: dict):
    """The architecture module the configuration names (a file without
    ``arch`` is an error)."""
    return importlib.import_module(f"bench.archs.{conf['arch']}")
