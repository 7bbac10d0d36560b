"""The benchmark's tests run on the CPU at a tiny geometry. They import the
benchmark's modules from ``bench/`` and the program from ``src/``."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def _fresh_pim_caches():
    """Drop the scheduler's caches around every test: a pipeline cached at
    one test's geometry is otherwise found again by a later test of
    another geometry in the same process, and fails there."""
    from repro.core import pim
    pim.clear_caches()
    yield
    pim.clear_caches()
