"""Puts the benchmark's own modules and the program's ``src`` on the path,
and keeps the compile cache of the runs these tests make in a temporary
directory, so that they leave no ``.jax-cache/`` in the checkout.

Run from the checkout root: ``python -m pytest bench/tests``.
"""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True, scope="session")
def compile_cache(tmp_path_factory):
    """``run_cell.CACHE_DIR`` for this session: a temporary directory."""
    import run_cell
    run_cell.CACHE_DIR = str(tmp_path_factory.mktemp("jax-cache"))
    return run_cell.CACHE_DIR
