"""The documents name only what exists: every repo path and every
``DYN_*`` environment name that ``README.md`` or a ``docs/`` page puts
in back-ticks is in the tree. A page that still teaches a deleted
script, or a variable nothing reads, fails here and not in a reader's
terminal."""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGES = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)
# where an environment name has to be read (or set by a test) to count
CODE_ROOTS = ("dynamo_tpu", "perf", "native", "tests", "chip_smoke.py")
SOURCE_SUFFIXES = ("py", "json", "jsonl", "md", "cc", "h", "sh", "toml",
                   "yml", "yaml")
# a bare file name is checked only where it would be a source file or a
# record of this repo (`spec.yaml`, `values.yaml` are the reader's own)
BARE_SUFFIXES = ("py", "json", "jsonl", "md", "cc", "h")
_PATH = re.compile(r"\.?[\w\-]+(?:/[\w.\-]+)*\.(?:%s)" % "|".join(SOURCE_SUFFIXES))
_ENV = re.compile(r"DYN_[A-Z0-9_]+")


@functools.lru_cache(maxsize=None)
def _ignored_dirs() -> frozenset:
    with open(os.path.join(REPO, ".gitignore")) as f:
        return frozenset(
            line.strip().rstrip("/") for line in f if line.strip().endswith("/")
        )


@functools.lru_cache(maxsize=None)
def _repo_files() -> frozenset:
    out = set()
    for dirpath, dirs, files in os.walk(REPO):
        dirs[:] = [
            d for d in dirs if d != ".git" and d not in _ignored_dirs()
        ]
        rel = os.path.relpath(dirpath, REPO)
        out.update(os.path.normpath(os.path.join(rel, f)) for f in files)
    return frozenset(out)


@functools.lru_cache(maxsize=None)
def _code_env_names() -> frozenset:
    names = set()
    for f in _repo_files():
        if f.split(os.sep)[0] in CODE_ROOTS:
            with open(os.path.join(REPO, f), errors="ignore") as fh:
                names.update(_ENV.findall(fh.read()))
    return frozenset(names)


def _names_a_file(path: str) -> bool:
    """The path itself, or the tail of a file's path: pages shorten
    ``dynamo_tpu/engine/engine.py`` to ``engine/engine.py`` or
    ``engine.py``."""
    path = os.path.normpath(path)
    return any(
        f == path or f.endswith(os.sep + path) for f in _repo_files()
    )


def drift(text: str) -> list[str]:
    """What a page names in back-ticks that the tree does not have."""
    # a name the page introduces under a `<placeholder>/` directory is a
    # run's output, and so is anything under a git-ignored directory
    outputs = set(re.findall(r"<\w+>/([\w.\-]+)", text))
    bad = []
    # fenced blocks count line by line, like the inline spans; a bare
    # name there is checked only if it is a script (`python tool.py`):
    # the rest are listings of what a run leaves behind
    fenced = re.findall(r"^```[^\n]*\n(.*?)^```", text, re.S | re.M)
    inline = re.findall(r"`([^`\n]+)`", re.sub(r"^```.*?^```", "", text,
                                                flags=re.S | re.M))
    spans = [(s, BARE_SUFFIXES) for s in inline] + [
        (ln, ("py",)) for block in fenced for ln in block.splitlines()
    ]
    for span, bare_suffixes in spans:
        words = span.split()
        for i, word in enumerate(words):
            if i and words[i - 1].startswith("--"):
                continue  # an option's value is the reader's own file
            word = re.sub(r"(::\S*|:\d[\d,\-]*)$", "", word.rstrip(".,;:)"))
            if _PATH.fullmatch(word):
                first, _, rest = word.partition("/")
                if first in _ignored_dirs() or word in outputs:
                    continue
                if not rest and word.rsplit(".", 1)[1] not in bare_suffixes:
                    continue
                if not _names_a_file(word):
                    bad.append(word)
    known = _code_env_names()
    for name in _ENV.findall(text):
        # a name ending in `_*` is a family: one member has to exist
        found = (
            any(k.startswith(name) for k in known)
            if name.endswith("_") else name in known
        )
        if not found:
            bad.append(name)
    return sorted(set(bad))


@pytest.mark.parametrize("page", PAGES)
def test_page_names_only_what_exists(page):
    with open(os.path.join(REPO, page)) as f:
        assert drift(f.read()) == [], (
            f"{page} names files or DYN_* variables that are not in the "
            "tree: repair the page (or the name)"
        )


def test_drift_is_seen():
    """The checker itself: a deleted script, a path whose directory is
    gone and an unknown variable are reported; shortened paths, a run's
    outputs and an option's value are not."""
    ghost = "DYN_" + "NO_SUCH"  # spelled apart: this file is scanned too
    page = (
        "Run `python no_such_script.py --quick`, read `gone_dir/tool.py` "
        f"and set `{ghost}_NAME=1` or one of `{ghost}_*`.\n"
        "See `engine/engine.py:12`, `tests/test_docs_drift.py::drift`, "
        "`README.md`; the run writes `<out>/made_by_a_run.json` and "
        "`made_by_a_run.json` is small; `--config mine.json`; "
        "`DYN_COMPILE_FENCE=fatal`.\n"
    )
    assert drift(page) == [
        f"{ghost}_", f"{ghost}_NAME", "gone_dir/tool.py",
        "no_such_script.py",
    ]
