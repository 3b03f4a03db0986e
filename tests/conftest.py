"""Shared test fixtures. NOTE: no XLA_FLAGS here — tests run on the single
CPU device; only launch/dryrun.py forces 512 placeholder devices (harness
contract). Multi-device tests spawn subprocesses instead.

Two suite-wide rules live here too:

- every test has a time limit (``TIME_LIMIT_S`` by default; raise it for one
  test with ``@pytest.mark.time_limit(seconds)``), so a hang fails that test
  instead of cutting the whole run;
- Hypothesis runs derandomized and without an example database, so every run
  draws the same examples.
"""
import os
import signal
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

try:
    from hypothesis import settings
except ModuleNotFoundError:  # optional dev dependency (requirements-dev.txt)
    pass
else:
    settings.register_profile("tier1", derandomize=True, database=None)
    settings.load_profile("tier1")

# Determinism for the whole suite.
np.random.seed(0)

TIME_LIMIT_S = 300


class TimeLimitExceeded(BaseException):
    """Raised in a test that outlives its limit. A BaseException, so neither
    the test's own ``except Exception`` nor Hypothesis's shrinker (which
    would re-run the example) takes it for an ordinary failure."""


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "time_limit(seconds): fail the test once a phase of it "
        f"(setup, call, teardown) runs past `seconds` (default {TIME_LIMIT_S})")


def _time_limited(item):
    marker = item.get_closest_marker("time_limit")
    limit = marker.args[0] if marker else TIME_LIMIT_S
    if threading.current_thread() is not threading.main_thread():
        return (yield)  # signals reach the main thread only

    def expire(signum, frame):
        raise TimeLimitExceeded(
            f"time limit: {item.nodeid} ran past {limit} s")

    previous = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


pytest_runtest_setup = pytest.hookimpl(wrapper=True)(_time_limited)
pytest_runtest_call = pytest.hookimpl(wrapper=True)(_time_limited)
pytest_runtest_teardown = pytest.hookimpl(wrapper=True)(_time_limited)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def run_subprocess_devices(code: str, n_devices: int = 8, timeout: int = 120):
    """Run `code` in a subprocess with n fake CPU devices (for mesh tests)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"subprocess failed\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    return proc.stdout
