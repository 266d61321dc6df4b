"""jax API drift guard.

The sharding API surface that has moved between jax releases
(abstract-mesh queries, ``jax.set_mesh``, ``shard_map``, ``axis_types=``,
``lax.axis_size``, ``cost_analysis``) is used only through wrappers in
repro/sharding/context.py; this module fails the build if direct uses
creep back in:

1. every module under src/repro imports cleanly (catches module-level
   AttributeErrors on the installed version), and
2. no source file outside the wrapper module references a known-drifting
   symbol directly.
"""
import importlib
import os
import pkgutil
import re

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")

# The single file allowed to use the moving jax API surface directly.
COMPAT_SHIM = os.path.join("repro", "sharding", "context.py")

# Symbols that differ across the jax versions this repo has met.  Each
# pattern matches a *direct* use; the compat shim wraps them all.
BANNED = [
    (r"get_abstract_mesh", "context.abstract_mesh_or_none()"),
    (r"jax\.set_mesh", "context.use_mesh(mesh)"),
    (r"jax\.shard_map", "context.shard_map(...)"),
    (r"experimental\.shard_map", "context.shard_map(...)"),
    (r"AxisType", "context.make_mesh(...)"),
    (r"axis_types\s*=", "context.make_mesh(...)"),
    (r"check_vma", "context.shard_map(...)"),
    (r"check_rep", "context.shard_map(...)"),
    (r"lax\.axis_size", "context.axis_size(name)"),
    (r"jax\.sharding\.use_mesh", "context.use_mesh(mesh)"),
    (r"jax\.typeof", "(no wrapper yet — add one to context.py)"),
    (r"\.cost_analysis\(\)", "context.compiled_cost_analysis(compiled)"),
]


def _src_py_files():
    for root, _dirs, files in os.walk(os.path.join(SRC, "repro")):
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(root, f)


def test_every_src_module_imports():
    import repro
    failures = []
    for mod in pkgutil.walk_packages(repro.__path__, prefix="repro."):
        try:
            importlib.import_module(mod.name)
        except Exception as e:          # noqa: BLE001 - report them all
            failures.append((mod.name, f"{type(e).__name__}: {e}"))
    assert not failures, failures


@pytest.mark.parametrize("pattern,replacement",
                         BANNED, ids=[b[0] for b in BANNED])
def test_no_drifting_jax_symbols_outside_compat_shim(pattern, replacement):
    rx = re.compile(pattern)
    hits = []
    for path in _src_py_files():
        if path.endswith(COMPAT_SHIM):
            continue
        with open(path) as f:
            for lineno, line in enumerate(f, 1):
                if rx.search(line):
                    hits.append(f"{os.path.relpath(path, SRC)}:{lineno}: "
                                f"{line.strip()}")
    assert not hits, (
        f"direct use of a version-drifting jax symbol; use {replacement} "
        f"from repro.sharding.context instead:\n" + "\n".join(hits))


def test_compat_shim_works_on_pinned_version():
    """The wrappers must all be callable on the installed jax."""
    from repro.sharding import context
    assert context.abstract_mesh_or_none() is None     # no mesh set
    mesh = context.make_mesh((1, 1), ("data", "model"))
    with context.use_mesh(mesh):
        pass
    assert isinstance(context.CAN_CONSTRAIN_UNDER_MANUAL, bool)
