"""Layering: the CFD framework imports nothing of the LM stack.

The framework is ``repro.api`` with ``cfd``, ``core``, ``sim``, ``obs``,
``jobs``, ``ckpt``, ``ft`` and ``kernels``.  The LM stack (``models``,
``configs``, ``train``, ``optim``, ``data``, ``serve``, ``dist`` and the
LM launchers) may import the framework, never the reverse, so either side
changes without breaking the other.  Of ``repro.launch`` the framework
uses only the shared utilities ``mesh`` and ``hlo_cost``.
"""
import os

from tests.helpers import REPO, imported_modules

FRAMEWORK = ("api.py", "cfd", "core", "sim", "obs", "jobs", "ckpt", "ft",
             "kernels")
LM = ("repro.models", "repro.configs", "repro.train", "repro.optim",
      "repro.data", "repro.serve", "repro.dist")
SHARED_LAUNCH = ("repro.launch.mesh", "repro.launch.hlo_cost")


def _framework_files():
    pkg = os.path.join(REPO, "src", "repro")
    for entry in FRAMEWORK:
        path = os.path.join(pkg, entry)
        if entry.endswith(".py"):
            yield path
            continue
        for root, _, files in os.walk(path):
            for fname in sorted(files):
                if fname.endswith(".py"):
                    yield os.path.join(root, fname)


def _under(mod: str, prefixes) -> bool:
    return any(mod == p or mod.startswith(p + ".") for p in prefixes)


def _forbidden(mod: str) -> bool:
    if _under(mod, LM):
        return True
    return (_under(mod, ("repro.launch",)) and mod != "repro.launch"
            and not _under(mod, SHARED_LAUNCH))


def test_framework_imports_no_lm_module():
    files = list(_framework_files())
    for entry in FRAMEWORK:           # the walk found every framework part
        assert any(os.path.relpath(f, os.path.join(REPO, "src", "repro"))
                   .startswith(entry) for f in files), entry
    offenders = sorted(
        f"{os.path.relpath(path, REPO)} imports {mod}"
        for path in files for mod in set(imported_modules(path))
        if _forbidden(mod))
    assert not offenders, (
        "framework modules must not import the LM stack:\n  "
        + "\n  ".join(offenders))
