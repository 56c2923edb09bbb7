"""Test fixtures.

JAX tests run on a virtual 8-device CPU mesh (the reference tests multi-GPU code
paths on CPU via `_fake_gpus`; we use XLA's host-platform device-count flag, see
SURVEY.md §4). Must be set before jax import — hence module-level os.environ here.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Dev-mode runtime sanitizers (ray_tpu/analysis/sanitizers.py) are ON for
# the whole tier-1 suite: lock-order cycle detection over the named
# core-plane locks, the io-loop watchdog, thread-affinity assertions.
# Must be set before any ray_tpu import (the gate is read at import time)
# and inherits into every daemon/worker subprocess the tests spawn.
os.environ.setdefault("RAY_TPU_SANITIZE", "1")
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8"
    ).strip()

# libtpu is installed: an unpinned JAX looks for a chip first (and on a TPU
# host this process would take it from the workers). Tests run on the virtual
# CPU mesh; the chip is reached only through the chip tool
# (`python chip_smoke.py`, `benchmarks/run.py`).
import jax

jax.config.update("jax_platforms", "cpu")

import faulthandler
import signal
import sys
from contextlib import contextmanager

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run"
    )
    config.addinivalue_line(
        "markers",
        "chaos(timeout=120): deterministic fault-injection tests "
        "(ray_tpu.testing.chaos). Its limit (default 120 s, timeout= "
        "overrides) replaces the one every test runs under "
        "(TEST_TIME_LIMIT_S).",
    )
    config.addinivalue_line(
        "markers",
        "lint: raylint static-analysis gate (whole-package run asserting "
        "zero unsuppressed findings) — one test node, selectable with "
        "-m lint.",
    )


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Sanitizer verdict for the whole suite: the driver process's own
    violations print here; daemon-side trips surface through the
    sanitizer_violations_total metric (scripts metrics / dashboards)."""
    try:
        from ray_tpu.analysis import sanitizers
    except Exception:  # noqa: BLE001 - never break reporting
        return
    terminalreporter.write_line(
        "raylint " + sanitizers.report(),
        red=bool(sanitizers.violation_counts()),
    )


def pytest_sessionfinish(session, exitstatus):
    """Deterministic sanitizer classes (lock-order cycles, affinity
    breaks) fail the run outright — they are real bugs wherever they
    fire. Loop stalls only print: on an oversubscribed CI box a slow
    thread schedule can legitimately delay a heartbeat."""
    try:
        from ray_tpu.analysis import sanitizers
    except Exception:  # noqa: BLE001
        return
    counts = sanitizers.violation_counts()
    if counts.get("lock_order") or counts.get("affinity"):
        session.exitstatus = 1


# Every test runs under a limit of its own, in each of set-up, call and
# tear-down: a hang (a blocked get on a dead ring, a cluster fixture that
# never comes up) then fails one test instead of eating the suite's budget.
# Seconds: three times the slowest honest test of a whole run under the
# driver's six workers (test_microbench_smoke, 94 s). chaos(timeout=...)
# overrides it for one test; a bare chaos mark keeps the 120 s it always had
# (fault injection that goes wrong hangs, and says so sooner). The limit
# bounds a hang local to one test, not a deadlock of the whole process:
# every later test of that worker would then spend its own limit, a phase.
TEST_TIME_LIMIT_S = 300


@contextmanager
def time_limit(seconds: int, what: str):
    """Raise TimeoutError in the main thread after ``seconds``, with every
    thread's stack dumped to stderr first so that the hang names itself.
    SIGALRM is handled in the main thread, which is where pytest (and an
    xdist worker) runs its tests; the framework's blocking waits are
    sleep-loops or lock waits, both of which the signal interrupts."""

    def on_alarm(signum, frame):
        # fd 2, not sys.stderr: capsys puts an object with no fileno there,
        # and pytest's fd capture shows fd 2 in the failed test's report
        faulthandler.dump_traceback(file=sys.__stderr__, all_threads=True)
        signal.alarm(seconds)  # the clean-up this raise sets off is cut too
        raise TimeoutError(f"{what} exceeded its {seconds}s limit")

    old = signal.signal(signal.SIGALRM, on_alarm)
    outer = signal.alarm(seconds)  # seconds an enclosing limit had left
    try:
        yield
    finally:
        signal.alarm(outer)
        signal.signal(signal.SIGALRM, old)


@pytest.hookimpl(hookwrapper=True)
def _under_time_limit(item):
    marker = item.get_closest_marker("chaos")
    limit = int(marker.kwargs.get("timeout", 120)) \
        if marker else TEST_TIME_LIMIT_S
    with time_limit(limit, item.nodeid):
        yield


pytest_runtest_setup = pytest_runtest_call = pytest_runtest_teardown = \
    _under_time_limit


@pytest.fixture
def ray_start_local():
    """In-process (local mode) runtime — fast unit-test fixture."""
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(local_mode=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_regular():
    """Real single-node cluster: GCS + raylet + workers in subprocesses
    (reference analog: python/ray/tests/conftest.py:351 ray_start_regular)."""
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def cpu_mesh8():
    """An 8-device CPU mesh standing in for a TPU slice."""
    import jax

    devices = jax.devices("cpu")
    assert len(devices) >= 8, "conftest must force 8 host devices"
    yield devices[:8]
