# NOTE: no XLA_FLAGS here on purpose — tests and benches must see the real
# single CPU device (the 512-device override belongs to launch/dryrun.py
# only).  Multi-device behaviour is tested via subprocesses
# (test_distributed_subprocess.py).
import os
import signal
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy cases excluded from the tier-1 fast run")


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="also run tests marked @pytest.mark.slow")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow; use --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _per_test_timeout(request):
    """Fail (instead of wedging CI) when a single test exceeds the budget.

    Enabled only when REPRO_TEST_TIMEOUT is set (scripts/check.sh sets it);
    uses SIGALRM, so main-thread only — which is how the suite runs.
    """
    budget = int(os.environ.get("REPRO_TEST_TIMEOUT", "0"))
    if budget <= 0 or os.name != "posix":
        yield
        return

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"test exceeded REPRO_TEST_TIMEOUT={budget}s: {request.node.nodeid}")

    prev = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(budget)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)
