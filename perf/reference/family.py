"""Which plain reference judges a configuration: the configuration's
``model_type`` names its family module.

``perf/reference/<model_type>.py`` (``-`` read as ``_``) where that file
exists; else the one module of ``perf/reference/`` that lists the
``model_type`` in its ``FAMILIES``; else an error that names both. There
is no fallback to another family's equations and no table here: a new
family enters as a file. What a family module has to give is written at
the top of ``model.py`` and in ``perf/README.md``.
"""

from __future__ import annotations

import importlib
import importlib.util
import pkgutil

PACKAGE = __name__.rpartition(".")[0]
REQUIRED = ("logits_fn", "PRECISIONS", "geometry")


class FamilyError(LookupError):
    pass


def _family(module):
    missing = [name for name in REQUIRED if not hasattr(module, name)]
    if missing:
        raise FamilyError(f"{module.__name__} is no family module: it lacks "
                          f"{', '.join(missing)} (the contract: model.py's top)")
    return module


def family_of(cfg: dict):
    """The family module of a configuration (its published keys)."""
    model_type = cfg.get("model_type")
    if not isinstance(model_type, str) or not model_type:
        raise FamilyError("the configuration states no model_type: no "
                          "reference can be chosen for it")
    stem = model_type.replace("-", "_")
    if stem.isidentifier() and importlib.util.find_spec(f"{PACKAGE}.{stem}"):
        return _family(importlib.import_module(f"{PACKAGE}.{stem}"))
    package = importlib.import_module(PACKAGE)
    covering = []
    for info in pkgutil.iter_modules(package.__path__):
        module = importlib.import_module(f"{PACKAGE}.{info.name}")
        if model_type in getattr(module, "FAMILIES", ()):
            covering.append(module)
    if len(covering) == 1:
        return _family(covering[0])
    where = f"perf/reference/{stem}.py"
    if covering:
        raise FamilyError(
            f"model_type {model_type!r}: no {where}, and FAMILIES lists it in "
            f"more than one module: {sorted(m.__name__ for m in covering)}")
    raise FamilyError(
        f"no reference for model_type {model_type!r}: no {where}, and no "
        f"module of perf/reference/ lists it in FAMILIES")
