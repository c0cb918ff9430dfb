"""Repo hygiene (fast tier): tracked bytecode must never come back.

Commit e7bee5b accidentally committed three ``__pycache__/*.pyc`` files;
.gitignore now covers them, and this test fails the fast tier if any
tracked bytecode reappears (``make lint`` runs the same check).
"""
import os
import subprocess

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_no_tracked_bytecode():
    try:
        out = subprocess.run(
            ["git", "ls-files", "*.pyc", "*.pyo", "__pycache__/*"],
            cwd=_ROOT, capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        pytest.skip("git unavailable")
    if out.returncode != 0:
        pytest.skip("not a git checkout")
    tracked = [l for l in out.stdout.splitlines() if l.strip()]
    assert not tracked, f"tracked bytecode files: {tracked}"


def test_serve_tree_has_zero_concurrency_findings():
    """The OCM05x asyncio lint (``occam.audit.lint_serve``) is a CI
    gate: the checked-in ``occam/serve`` tree must carry zero findings —
    not merely zero errors — so a blocking call or unguarded cross-
    thread mutation fails the fast tier the commit it appears."""
    import sys

    sys.path.insert(0, os.path.join(_ROOT, "src"))
    try:
        from repro.occam.audit import lint_serve
    finally:
        sys.path.pop(0)
    report = lint_serve()
    assert not report.findings, report.summary()


def test_gitignore_covers_caches():
    path = os.path.join(_ROOT, ".gitignore")
    assert os.path.exists(path), ".gitignore missing"
    with open(path) as f:
        rules = f.read()
    for rule in ("__pycache__/", "*.pyc", ".pytest_cache/", "results/*.tmp",
                 ".hypothesis/", ".jax_cache/"):
        assert rule in rules, f".gitignore lost the {rule!r} rule"
