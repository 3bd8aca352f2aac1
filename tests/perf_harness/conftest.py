"""A family module that exists only as a file outside ``perf/``: the
fixture puts a temporary directory on ``perf.reference``'s search path,
so a test can add ``<model_type>.py`` there and nothing under ``perf/``
is edited — what a later PR does with a new file in ``perf/reference/``."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

# A family of one equation, in numpy: the logits at a position put the
# id after the token there first, in "a8" the id after that. It records
# every call, so a test sees whose logits_fn ran.
TOY_FAMILY = '''
import numpy as np

PRECISIONS = ("f32", "a8")
CALLS = []


def geometry(cfg):
    return dict(D=cfg["hidden_size"], V=cfg["vocab_size"], H=4, Hk=2, Dh=16)


def logits_fn(cfg, precision="f32"):
    V = cfg["vocab_size"]

    def run(seed, tokens, lengths, at):
        CALLS.append((precision, seed, tuple(np.shape(tokens))))
        here = np.take_along_axis(np.asarray(tokens), np.asarray(at), axis=1)
        first = (here + (2 if precision == "a8" else 1)) % V
        return -np.abs(np.arange(V)[None, None, :] - first[:, :, None]).astype(np.float32)

    return run
'''


@pytest.fixture
def family_files(tmp_path, monkeypatch):
    """``add(stem, more)`` writes the toy family, and ``more`` after it,
    as ``<stem>.py`` where ``perf.reference`` finds it for this test only;
    returns the directory."""
    import perf.reference as package

    monkeypatch.setattr(package, "__path__", [*package.__path__, str(tmp_path)])
    added = []

    def add(stem: str, more: str = "") -> str:
        (tmp_path / f"{stem}.py").write_text(TOY_FAMILY + more)
        added.append(f"perf.reference.{stem}")
        return str(tmp_path)

    yield add
    for name in added:
        sys.modules.pop(name, None)
