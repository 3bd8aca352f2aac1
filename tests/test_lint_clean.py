"""Self-clean gate: `dynamo-tpu lint` over dynamo_tpu/ must report zero
unsuppressed findings — per-file rules AND the whole-program DL1xx pass
(call graph + taints). This test IS the CI wiring — it runs inside the
tier-1 pytest command on every change, so a new blocking call, hidden
transitive device sync, or undeclared cross-thread write fails the
merge without any extra CI configuration. It also measures the warm
path: a second run through the on-disk result cache must finish in
under 5s, which is what keeps whole-repo lint viable inside tier-1."""

import time
from pathlib import Path

import pytest

from dynamo_tpu.analysis import (
    format_text,
    lint_paths,
    load_config,
    unsuppressed,
)
from dynamo_tpu.analysis.cache import LintCache

REPO = Path(__file__).resolve().parents[1]

# the self-clean contract extends beyond the package: the
# test-infrastructure helpers run the same async/engine machinery, so a
# blocking call or hidden sync there hides what the package's own rules
# protect (fixture data under tests/data stays out — violating fixtures
# exist to violate)
EXTRA_CLEAN_PATHS = [
    str(REPO / "tests" / "cli_harness.py"),
    str(REPO / "tests" / "prom_parser.py"),
    str(REPO / "tests" / "sdk_graph.py"),
]


@pytest.mark.pre_merge
def test_repo_is_lint_clean():
    cfg = load_config(start=str(REPO))
    cache = LintCache(REPO / ".dynalint_cache")
    findings = lint_paths(cfg["include"], config=cfg, cache=cache)
    live = unsuppressed(findings)
    assert live == [], (
        "unsuppressed dynalint findings (fix them, or waive a deliberate "
        "pattern in place with `# dynalint: disable=<rule> — why`; declare "
        "a deliberate cross-thread write with `# dynalint: handoff=<why>`"
        "):\n" + format_text(findings)
    )


@pytest.mark.pre_merge
def test_test_helpers_are_lint_clean():
    # a separate lint_paths call (not config `include`): these files
    # live outside the package root, and folding them into the main
    # walk would change the whole-program pass's module universe (and
    # its cache key) for every other consumer
    for p in EXTRA_CLEAN_PATHS:
        assert Path(p).exists(), f"extra clean path vanished: {p}"
    cfg = load_config(start=str(REPO))
    cache = LintCache(REPO / ".dynalint_cache")
    findings = lint_paths(EXTRA_CLEAN_PATHS, config=cfg, cache=cache)
    live = unsuppressed(findings)
    assert live == [], (
        "unsuppressed dynalint findings in the tests' helpers:\n"
        + format_text(findings)
    )


@pytest.mark.pre_merge
def test_warm_whole_repo_lint_under_5s():
    # the acceptance bound for keeping lint inside tier-1: with the
    # cache primed, a full-repo lint hits the per-file AND program
    # entries and never parses a file. Prime explicitly so the test
    # holds standalone, then measure a fresh cache instance (true
    # cold-process warm path: read cache.json, hash files, look up).
    cfg = load_config(start=str(REPO))
    lint_paths(cfg["include"], config=cfg,
               cache=LintCache(REPO / ".dynalint_cache"))
    cache = LintCache(REPO / ".dynalint_cache")
    t0 = time.monotonic()
    findings = lint_paths(cfg["include"], config=cfg, cache=cache)
    dt = time.monotonic() - t0
    assert unsuppressed(findings) == []
    assert cache.misses == 0, (
        f"warm run missed the cache {cache.misses} time(s) — key drift?"
    )
    assert dt < 5.0, f"warm whole-repo lint took {dt:.1f}s (budget 5s)"


@pytest.mark.pre_merge
def test_lint_actually_scanned_the_package():
    # guard against a silently-empty walk (bad include/exclude config)
    from dynamo_tpu.analysis import iter_files

    cfg = load_config(start=str(REPO))
    files = iter_files(cfg["include"], exclude=cfg["exclude"])
    assert len(files) > 50, "walk found suspiciously few files"
    names = {f.name for f in files}
    assert "engine.py" in names and "service.py" in names
    assert not any("native" in str(f) for f in files), "exclude broken"


def test_suppressions_carry_justifications():
    # every in-tree waiver must say why: a bare disable comment rots
    import re

    cfg = load_config(start=str(REPO))
    pat = re.compile(r"#\s*dynalint:\s*disable=[\w\-, ]+")
    from dynamo_tpu.analysis import iter_files

    scope = iter_files(cfg["include"], exclude=cfg["exclude"])
    scope += [Path(p) for p in EXTRA_CLEAN_PATHS]
    for f in scope:
        for i, line in enumerate(f.read_text().splitlines(), start=1):
            m = pat.search(line)
            if m is None:
                continue
            comment_and_code = line[m.end():].strip(" -—:")
            before = line[: m.start()].strip()
            assert comment_and_code or _nearby_comment(f, i), (
                f"{f}:{i}: suppression without justification "
                f"(add `— why` after the disable, or a comment above)"
            )
            assert before, (
                f"{f}:{i}: suppression on a comment-only line does "
                "nothing (it must share the violating line)"
            )


def _nearby_comment(path: Path, line: int, window: int = 3) -> bool:
    lines = path.read_text().splitlines()
    lo = max(0, line - 1 - window)
    return any(ln.strip().startswith("#") for ln in lines[lo:line - 1])
