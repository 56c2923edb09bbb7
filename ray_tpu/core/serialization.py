"""Serialization: cloudpickle + pickle-protocol-5 out-of-band buffers.

Parity: the reference serializes with vendored cloudpickle and moves large numpy /
Arrow buffers out-of-band so they land in plasma with zero copies
(python/ray/_private/serialization.py). We do the same with stock cloudpickle:
``serialize`` returns a small in-band payload plus a list of raw buffers; the object
store writes buffers contiguously into shared memory and ``deserialize`` maps them
back with zero copies (numpy arrays reconstruct over the shm pages).

JAX additions (TPU-native): device arrays are pulled to host as numpy before
serialization (``jax.device_get``); on deserialization the consumer decides whether to
``device_put`` into HBM (Data layer prefetching does this explicitly).
"""

from __future__ import annotations

import io
import pickle
import sys
import threading
import types
from typing import Any, Dict, List, Tuple

import cloudpickle

from ray_tpu.analysis import sanitizers as _san
from ray_tpu.core.refs import ObjectRef


def user_module_for_by_value(obj):
    """If ``obj`` is a function/class from a module workers likely can't import
    (user scripts, test files), return that module so it can be registered for
    by-value pickling; installed packages, stdlib and ray_tpu itself pickle by
    reference. Mirrors the reference's function-export semantics
    (python/ray/_private/function_manager.py) for task/actor *arguments* too.
    """
    import sys
    import sysconfig

    if not isinstance(obj, (types.FunctionType, type)):
        return None
    mod_name = getattr(obj, "__module__", "") or ""
    if mod_name in ("", "__main__", "builtins"):
        return None
    mod = sys.modules.get(mod_name)
    if mod is None:
        return None
    f = getattr(mod, "__file__", "") or ""
    stdlib = sysconfig.get_paths().get("stdlib", "//")
    if (
        not f
        or "site-packages" in f
        or "dist-packages" in f
        or f.startswith(stdlib)
        or "/ray_tpu/" in f.replace("\\", "/")
    ):
        return None
    return mod

# Buffers smaller than this stay in-band (copying beats bookkeeping).
_OOB_THRESHOLD = 1 << 16  # 64 KiB


class SerializedObject:
    __slots__ = ("payload", "buffers", "contained_refs")

    def __init__(self, payload: bytes, buffers: List[memoryview], contained_refs):
        self.payload = payload
        self.buffers = buffers
        self.contained_refs = contained_refs

    def total_bytes(self) -> int:
        return len(self.payload) + sum(b.nbytes for b in self.buffers)

    def __reduce_ex__(self, protocol):
        """Wire transport (core/rpc.py v2 frames): payload and buffers
        travel as protocol-5 ``PickleBuffer``s, so the frame encoder writes
        them straight from their source memory (the user's numpy array, a
        shm mapping) into the frame's out-of-band segment table and the
        receiver maps them back as zero-copy views over the frame body —
        no ``to_bytes`` flatten on send, no ``from_buffer`` re-parse on
        receive. ``contained_refs`` intentionally does not cross the wire:
        nested ObjectRefs re-register when the payload is deserialized."""
        if protocol >= 5:
            return (
                _wire_serialized,
                (
                    pickle.PickleBuffer(self.payload),
                    tuple(pickle.PickleBuffer(b) for b in self.buffers),
                ),
            )
        return (
            _wire_serialized,
            (bytes(self.payload), tuple(bytes(b) for b in self.buffers)),
        )

    def to_bytes(self) -> bytes:
        """Flatten to a single framed byte string (for wire transfer / shm)."""
        out = io.BytesIO()
        out.write(len(self.payload).to_bytes(8, "little"))
        out.write(len(self.buffers).to_bytes(4, "little"))
        for b in self.buffers:
            out.write(b.nbytes.to_bytes(8, "little"))
        out.write(self.payload)
        for b in self.buffers:
            out.write(b)
        return out.getvalue()

    @staticmethod
    def from_buffer(data) -> "SerializedObject":
        """Zero-copy parse of the framing produced by ``to_bytes``.

        ``data`` may be bytes or a writable/readable memoryview over shared memory;
        the returned buffers are sub-views, not copies.
        """
        mv = memoryview(data)
        plen = int.from_bytes(mv[:8], "little")
        nbuf = int.from_bytes(mv[8:12], "little")
        off = 12
        sizes = []
        for _ in range(nbuf):
            sizes.append(int.from_bytes(mv[off : off + 8], "little"))
            off += 8
        payload = bytes(mv[off : off + plen])
        off += plen
        buffers = []
        for s in sizes:
            buffers.append(mv[off : off + s])
            off += s
        return SerializedObject(payload, buffers, [])


def _wire_serialized(payload, buffers) -> "SerializedObject":
    """Rebuild a SerializedObject on the receiving side of a wire frame.
    ``payload``/``buffers`` arrive as PickleBuffers resolved to zero-copy
    views over the frame body (or plain bytes from a pre-v5 pickler)."""
    return SerializedObject(
        payload if isinstance(payload, (bytes, memoryview))
        else memoryview(payload),
        [b if isinstance(b, memoryview) else memoryview(b) for b in buffers],
        [],
    )


def _jax_array_type():
    """``jax.Array`` if this process has imported JAX, else ``None``: a value
    can only hold a jax array where JAX is loaded, and importing it to ask
    costs every pooled worker ~3 s before its first result leaves (PR 40:
    each of ``Dataset.split``'s producers held its block that long)."""
    jax = sys.modules.get("jax")
    return getattr(jax, "Array", None)  # None too while jax is mid-import


def _device_get_if_jax(value):
    """Move jax.Array leaves to host numpy (TPU HBM → host before shm write)."""
    array_type = _jax_array_type()
    if array_type is not None and isinstance(value, array_type):
        import numpy as np

        return np.asarray(value)
    return value


# cloudpickle.register_pickle_by_value mutates process-global state; concurrent
# serialize() calls must not unregister a module while another dump is mid-
# flight (advisor finding r2). Registrations are reference-counted under a lock.
_BY_VALUE_LOCK = _san.make_lock("core.serialization.by_value")
_BY_VALUE_COUNTS: Dict[str, int] = {}


def _register_by_value(mod) -> bool:
    with _BY_VALUE_LOCK:
        n = _BY_VALUE_COUNTS.get(mod.__name__, 0)
        if n == 0:
            try:
                cloudpickle.register_pickle_by_value(mod)
            except Exception:  # noqa: BLE001 - fall back to by-reference
                return False
        _BY_VALUE_COUNTS[mod.__name__] = n + 1
        return True


def _unregister_by_value(mod) -> None:
    with _BY_VALUE_LOCK:
        n = _BY_VALUE_COUNTS.get(mod.__name__, 0)
        if n <= 1:
            _BY_VALUE_COUNTS.pop(mod.__name__, None)
            try:
                cloudpickle.unregister_pickle_by_value(mod)
            except Exception:  # noqa: BLE001
                pass
        else:
            _BY_VALUE_COUNTS[mod.__name__] = n - 1


class _FrameworkPickler(cloudpickle.CloudPickler):
    """Per-call pickler. Deliberately a MODULE-level class: a class defined
    inside serialize() sits in a reference cycle (class → methods → closure
    cells → contained_refs/buffers), so every serialized ObjectRef and
    out-of-band buffer stayed alive until a gen-2 GC — which kept 'dead'
    refs counted in the owner and deferred distributed frees indefinitely."""

    def __init__(self, file, buffer_callback, contained_refs, registered_mods,
                 registered_names):
        # buffer_callback must be a plain function, NOT a bound method of
        # self — the C pickler holding a bound method closes a cycle
        # (pickler → method → pickler) that defers teardown to gen-2 GC,
        # which is exactly the retention this class exists to avoid.
        super().__init__(file, protocol=5, buffer_callback=buffer_callback)
        self._contained_refs = contained_refs
        self._registered_mods = registered_mods
        self._registered_names = registered_names

    def persistent_id(self, obj):
        return None

    def reducer_override(self, obj):
        if isinstance(obj, ObjectRef):
            self._contained_refs.append(obj)
        # jax arrays nested inside containers
        array_type = _jax_array_type()
        if array_type is not None and isinstance(obj, array_type):
            import numpy as np

            arr = np.asarray(obj)
            return (_restore_ndarray,
                    (pickle.PickleBuffer(arr), arr.dtype.str, arr.shape))
        # Functions/classes from user modules (test files, scripts) must
        # travel by VALUE — the worker can't import their module. Register
        # the module before delegating so cloudpickle's own reduce path
        # sees it in the by-value registry.
        mod = user_module_for_by_value(obj)
        if mod is not None and mod.__name__ not in self._registered_names:
            if _register_by_value(mod):
                self._registered_mods.append(mod)
                self._registered_names.add(mod.__name__)
        # Delegate to cloudpickle so locally-defined / unimportable functions
        # and classes are still pickled by value (the whole point of using
        # CloudPickler); returning NotImplemented here would silently fall
        # back to stdlib pickle for them.
        return super().reducer_override(obj)


def serialize(value: Any) -> SerializedObject:
    buffers: List[memoryview] = []
    contained_refs: List[ObjectRef] = []
    registered_mods: List[Any] = []

    value = _device_get_if_jax(value)

    def _buffer_cb(buf: pickle.PickleBuffer):
        raw = buf.raw()
        if raw.nbytes < _OOB_THRESHOLD:
            return True  # keep in-band
        buffers.append(raw)
        return False

    out = io.BytesIO()
    p = _FrameworkPickler(out, _buffer_cb, contained_refs, registered_mods,
                          set())
    try:
        p.dump(value)
    finally:
        for mod in registered_mods:
            _unregister_by_value(mod)
    return SerializedObject(out.getvalue(), buffers, contained_refs)


def _restore_ndarray(buf, dtype_str, shape):
    import numpy as np

    return np.frombuffer(buf, dtype=np.dtype(dtype_str)).reshape(shape)


def deserialize(obj: SerializedObject) -> Any:
    return pickle.loads(obj.payload, buffers=obj.buffers)


def dumps(value: Any) -> bytes:
    """One-shot serialize to a flat byte string."""
    return serialize(value).to_bytes()


def loads(data) -> Any:
    return deserialize(SerializedObject.from_buffer(data))
