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
# CPU mesh; the chip is reached only by `python chip_smoke.py` / bench.py.
import jax

jax.config.update("jax_platforms", "cpu")

import signal

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run"
    )
    config.addinivalue_line(
        "markers",
        "chaos(timeout=120): deterministic fault-injection tests "
        "(ray_tpu.testing.chaos). Run in tier-1 under a per-test SIGALRM "
        "guard so a regression that re-introduces a hang fails fast "
        "instead of stalling the whole suite.",
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


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """Per-test timeout guard for chaos-marked tests: fault-injection bugs
    typically manifest as hangs (a blocked get on a dead ring), and the
    suite-level timeout would eat the whole tier-1 budget. SIGALRM fires in
    the main thread; the framework's blocking waits are sleep-loops, so the
    alarm interrupts them."""
    marker = item.get_closest_marker("chaos")
    if marker is None or not hasattr(signal, "SIGALRM"):
        yield
        return
    limit = int(marker.kwargs.get("timeout", 120))

    def on_alarm(signum, frame):
        raise TimeoutError(
            f"chaos test exceeded its {limit}s guard (stuck failure path?)"
        )

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(limit)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


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
