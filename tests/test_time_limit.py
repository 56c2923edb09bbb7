"""The per-test time limit of ``conftest.py``: the helper alone, and the
hooks around set-up, call and tear-down as an xdist worker runs them."""

import os
import signal
import subprocess
import sys
import textwrap
import threading

import pytest
from conftest import TEST_TIME_LIMIT_S, time_limit


def test_time_limit_raises_and_dumps_the_waiting_frame(capfd):
    def waits_for_nothing():
        threading.Event().wait()

    with pytest.raises(TimeoutError, match="scratch exceeded its 1s limit"):
        with time_limit(1, "scratch"):
            waits_for_nothing()
    err = capfd.readouterr().err
    assert "most recent call first" in err
    assert "waits_for_nothing" in err
    # this test's own limit was armed by the hook and outlives the inner one
    left = signal.alarm(0)
    signal.alarm(left)
    assert 0 < left <= TEST_TIME_LIMIT_S


_SCRATCH = """
    import threading

    import pytest


    @pytest.fixture
    def never_up():
        threading.Event().wait()


    @pytest.fixture
    def never_down():
        yield
        threading.Event().wait()


    @pytest.mark.chaos(timeout=1)
    def test_blocked_in_set_up(never_up):
        pass


    @pytest.mark.chaos(timeout=1)
    def test_blocked_in_call():
        threading.Event().wait()


    @pytest.mark.chaos(timeout=1)
    def test_blocked_in_tear_down(never_down):
        pass


    def test_not_blocked():
        assert threading.current_thread() is threading.main_thread()
"""


def test_blocked_set_up_call_and_tear_down_are_cut_under_xdist(tmp_path):
    """The driver's shape (``-p xdist -n <k>``): the alarm fires in the
    worker's main thread in each phase, ``chaos(timeout=...)`` overrides
    the constant, and the run goes on to the next test."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    scratch = tmp_path / "test_scratch.py"
    scratch.write_text(textwrap.dedent(_SCRATCH))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [tests_dir, os.path.dirname(tests_dir), env.get("PYTHONPATH", "")])
    run = subprocess.run(
        [sys.executable, "-m", "pytest", str(scratch), "-q", "-p", "conftest",
         "-p", "no:cacheprovider", "-p", "xdist", "-n", "2",
         "--rootdir", str(tmp_path)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=100)
    out = run.stdout + run.stderr
    assert run.returncode == 1, out
    assert "2 passed" in out and "1 failed" in out and "2 errors" in out, out
    for name in ("test_blocked_in_set_up", "test_blocked_in_call",
                 "test_blocked_in_tear_down"):
        assert f"{name} exceeded its 1s limit" in out, out
    # the dump of every thread's stack, naming the frame that waited
    assert out.count("most recent call first") >= 3, out
    assert "in never_up" in out and "in never_down" in out, out
