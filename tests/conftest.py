import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(autouse=True)
def _fresh_pim_stats():
    """Zero the pim instrumentation counters (COLUMN_STATS / SCHED_STATS /
    RUNNER_STATS) before every test so stats-asserting tests are
    order-independent — any test may touch the cached schedule paths.

    Also drop the scheduler caches: each cached plan keeps a compiled
    program alive, and one worker running many tests would otherwise
    gather them until the process runs out of memory mappings and the next
    XLA compile crashes it. Drop them again after the test: a worker may
    next run a test of ``bench/tests``, which compiles every pipeline the
    scheduler's cache holds for its own device geometry."""
    import repro.core.pim as pim

    pim.reset_stats()
    pim.clear_caches()
    yield
    pim.clear_caches()

try:  # hypothesis is optional: clean environments still run the example tests
    from hypothesis import settings, HealthCheck
except ImportError:
    pass
else:
    _suppress = [HealthCheck.too_slow, HealthCheck.data_too_large]
    # derandomize: CI failures must reproduce from the fixed profile seed.
    settings.register_profile(
        "ci", deadline=None, max_examples=25, derandomize=True,
        suppress_health_check=_suppress)
    # Heavier sweep for the differential harness (CI runs it explicitly:
    # HYPOTHESIS_PROFILE=differential pytest tests/test_pim_differential.py).
    settings.register_profile(
        "differential", deadline=None, max_examples=200, derandomize=True,
        suppress_health_check=_suppress)
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))
