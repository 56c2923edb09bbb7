"""``train/backend_init``: this process's first JAX backend coming up, as a span.

JAX brings a backend up where the program first asks for its devices — in a
train worker the user's own ``jax.devices()``, in the loop — and says so in
two DEBUG lines of ``jax._src.xla_bridge``'s logger, the first and the last
statement of ``_init_backend``: ``Initializing backend '<platform>'`` and
``Backend '<platform>' initialized``. The filter below takes those two
records on the thread that made them (the loop's, so the loop's task and
trace) and turns them into one span; it calls nothing of JAX's, so the
bring-up stays where and what it was. To be handed DEBUG records the logger
is lowered to DEBUG until the pair was seen; every record below the level it
had is stopped in the filter, so no log of the process grows by a line.
"""

from __future__ import annotations

import logging
import sys
import time
from typing import Optional

from ray_tpu.analysis import sanitizers as _san
from ray_tpu.tracing import names
from ray_tpu.tracing.events import record_named

_LOGGER = "jax._src.xla_bridge"
_START, _END = "Initializing backend '%s'", "Backend '%s' initialized"
_install_lock = _san.make_lock("tracing.backend_init")
_installed = False


def _device_count() -> Optional[int]:
    """The devices of the backend `_init_backend` is about to return: its
    own local, read from the frame that logs the closing line (asking JAX
    here would re-enter `backends()` under its lock)."""
    frame = sys._getframe(1)
    while frame is not None and frame.f_code.co_name != "_init_backend":
        frame = frame.f_back
    try:
        return frame.f_locals["backend"].device_count()
    except Exception:  # noqa: BLE001 - another JAX: the span goes without
        return None


class _Observer(logging.Filter):
    def __init__(self, logger: logging.Logger, rank: int):
        super().__init__()
        self._logger = logger
        self._rank = rank
        self._was = logger.level               # restored once the pair is seen
        self._passes = logger.getEffectiveLevel()
        self._t0: Optional[float] = None
        self._done = False

    def filter(self, record: logging.LogRecord) -> bool:
        if self._done:
            return True
        if record.msg == _START and self._t0 is None:
            self._t0 = time.perf_counter()
        elif record.msg == _END and self._t0 is not None:
            seconds = time.perf_counter() - self._t0
            self._done = True
            self._logger.setLevel(self._was)
            record_named(names.TRAIN_BACKEND_INIT, {
                "rank": self._rank, "platform": record.args[0],
                "devices": _device_count(), "seconds": seconds}, dur=seconds)
        return record.levelno >= self._passes


def record_backend_init(rank: int) -> None:
    """Record this process's first backend initialisation from now on, if it
    is still to come (idempotent; a process whose backends are up, or that
    already watches, registers nothing)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        _installed = True
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            return
        logger = logging.getLogger(_LOGGER)
        logger.addFilter(_Observer(logger, rank))
        logger.setLevel(logging.DEBUG)
