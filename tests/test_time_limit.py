"""The suite's per-test time limit (conftest.py): a test that runs past its
limit fails with the limit's message, and the run goes on to the next test."""
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(__file__)

SLEEPER = '''
import time

import pytest


@pytest.mark.time_limit(1)
def test_sleeps_past_its_limit():
    time.sleep(30)


def test_runs_after_the_limit():
    assert True
'''


def test_time_limit_fails_one_test_and_the_run_goes_on(tmp_path):
    shutil.copy(os.path.join(HERE, "conftest.py"), tmp_path / "conftest.py")
    (tmp_path / "test_sleeper.py").write_text(SLEEPER)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "--rootdir", str(tmp_path),
         str(tmp_path / "test_sleeper.py")],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 1, out
    assert "FAILED test_sleeper.py::test_sleeps_past_its_limit" in out, out
    assert ("time limit: test_sleeper.py::test_sleeps_past_its_limit ran past "
            "1 s") in out, out
    assert "PASSED test_sleeper.py::test_runs_after_the_limit" in out, out
    assert "1 failed, 1 passed" in out, out
