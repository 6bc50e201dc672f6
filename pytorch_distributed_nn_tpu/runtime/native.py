"""ctypes bindings for the native runtime (native/libtpunative.so).

Two components, each the TPU-framework replacement for a C++ piece the
reference borrowed from torch (SURVEY.md §2b):

- :class:`StoreServer` / :class:`StoreClient` — the c10d-TCPStore
  equivalent: key-value rendezvous with blocking waits, atomic counters
  (rank assignment), and barriers. Used by multi-process launch when no
  JAX coordinator is running, and by the failure detector's heartbeats.
- :func:`gen_images` / :func:`gen_lm` / :func:`gen_templates` — the
  threaded native data generator behind the ``native`` dataset backend.

The library is built with ``make`` on first load in each process (g++
is in the image; pybind11 is not, hence the C ABI).
"""

from __future__ import annotations

import ctypes
import subprocess
import threading
from pathlib import Path

import numpy as np

from pytorch_distributed_nn_tpu.runtime import chaos

_NATIVE_DIR = Path(__file__).resolve().parents[2] / "native"
_LIB_PATH = _NATIVE_DIR / "libtpunative.so"
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None


class NativeUnavailable(RuntimeError):
    pass


def load_library() -> ctypes.CDLL:
    """Build (``make``: nothing to do when current) and load the native
    library; cached. Always asking ``make`` means a library left in the
    tree by an earlier build is never loaded stale: what runs was built
    from ``store.cpp`` / ``datagen.cpp`` as they stand."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        try:
            subprocess.run(["make", "-C", str(_NATIVE_DIR)],
                           check=True, capture_output=True)
        except (subprocess.CalledProcessError, OSError) as e:
            out = getattr(e, "stderr", b"")
            raise NativeUnavailable(
                f"native build failed: {e}: "
                f"{out.decode() if isinstance(out, bytes) else out}"
            ) from e
        lib = ctypes.CDLL(str(_LIB_PATH))
        _declare(lib)
        _lib = lib
        return lib


def available() -> bool:
    try:
        load_library()
        return True
    except NativeUnavailable:
        return False


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.tpustore_server_start.restype = c.c_void_p
    lib.tpustore_server_start.argtypes = [c.c_int]
    lib.tpustore_server_port.restype = c.c_int
    lib.tpustore_server_port.argtypes = [c.c_void_p]
    lib.tpustore_server_stop.argtypes = [c.c_void_p]
    lib.tpustore_connect.restype = c.c_void_p
    lib.tpustore_connect.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.tpustore_disconnect.argtypes = [c.c_void_p]
    lib.tpustore_set.restype = c.c_int
    lib.tpustore_set.argtypes = [c.c_void_p, c.c_char_p,
                                 c.POINTER(c.c_uint8), c.c_int]
    lib.tpustore_get.restype = c.c_int
    lib.tpustore_get.argtypes = [c.c_void_p, c.c_char_p,
                                 c.POINTER(c.c_uint8), c.c_int, c.c_int64]
    lib.tpustore_add.restype = c.c_int64
    lib.tpustore_add.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.tpustore_check.restype = c.c_int
    lib.tpustore_check.argtypes = [c.c_void_p, c.c_char_p]
    lib.tpustore_delete.restype = c.c_int
    lib.tpustore_delete.argtypes = [c.c_void_p, c.c_char_p]

    u64, i64, i32 = c.c_uint64, c.c_int64, c.c_int32
    fp = c.POINTER(c.c_float)
    ip = c.POINTER(i32)
    lib.datagen_templates.argtypes = [u64, i64, i64, fp, c.c_int]
    lib.datagen_images.argtypes = [u64, u64, i64, i64, i64, c.c_float,
                                   fp, fp, ip, c.c_int]
    lib.datagen_lm.argtypes = [u64, u64, i64, i64, i64, i64, i64,
                               c.c_float, ip, c.c_int]


# ---------------------------------------------------------------------------
# Rendezvous store
# ---------------------------------------------------------------------------

class StoreServer:
    """Hosts the store (one per job, on the coordinator)."""

    def __init__(self, port: int = 0) -> None:
        self._lib = load_library()
        self._h = self._lib.tpustore_server_start(port)
        if not self._h:
            raise OSError(f"could not bind store server on port {port}")
        self.port = self._lib.tpustore_server_port(self._h)

    def stop(self) -> None:
        if self._h:
            self._lib.tpustore_server_stop(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()


class StoreClient:
    """One connection to the store; thread-safe per handle."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 connect_timeout_ms: int = 30_000) -> None:
        self._lib = load_library()
        self._h = self._lib.tpustore_connect(
            host.encode(), port, connect_timeout_ms
        )
        if not self._h:
            raise ConnectionError(f"could not connect to store at "
                                  f"{host}:{port}")
        self._barrier_round: dict[str, int] = {}

    def set(self, key: str, value: bytes) -> None:
        chaos.on_store_op("set", key)  # store_flaky injection point
        buf = (ctypes.c_uint8 * len(value)).from_buffer_copy(value or b"\0")
        rc = self._lib.tpustore_set(self._h, key.encode(), buf, len(value))
        if rc != 0:
            raise OSError(f"store set({key!r}) failed rc={rc}")

    def get(self, key: str, *, timeout_ms: int = -1,
            max_bytes: int = 1 << 20) -> bytes:
        """Blocking wait for ``key`` (timeout_ms < 0 waits forever)."""
        chaos.on_store_op("get", key)  # store_flaky injection point
        cap = max_bytes
        while True:
            buf = (ctypes.c_uint8 * cap)()
            rc = self._lib.tpustore_get(self._h, key.encode(), buf, cap,
                                        timeout_ms)
            if rc == -3 and cap < (1 << 30):  # value larger than cap
                cap *= 4
                continue
            if rc == -2:
                raise TimeoutError(f"store get({key!r}) timed out")
            if rc < 0:
                raise OSError(f"store get({key!r}) failed rc={rc}")
            return bytes(buf[:rc])

    def add(self, key: str, delta: int = 1) -> int:
        chaos.on_store_op("add", key)  # store_flaky injection point
        out = self._lib.tpustore_add(self._h, key.encode(), delta)
        if out == -(2 ** 63):
            raise OSError(f"store add({key!r}) failed")
        return out

    def check(self, key: str) -> bool:
        chaos.on_store_op("check", key)  # store_flaky injection point
        rc = self._lib.tpustore_check(self._h, key.encode())
        if rc < 0:
            raise OSError(f"store check({key!r}) failed")
        return rc == 1

    def delete(self, key: str) -> None:
        chaos.on_store_op("delete", key)  # store_flaky injection point
        if self._lib.tpustore_delete(self._h, key.encode()) != 0:
            raise OSError(f"store delete({key!r}) failed")

    def barrier(self, name: str, world_size: int, *,
                timeout_ms: int = 60_000) -> None:
        """c10d-style store barrier: count arrivals, wait for the flag.

        Reusable: each call advances a per-name round (all participants
        must call it the same number of times, the usual contract), so
        per-step/per-epoch barriers don't see stale flags.
        """
        rnd = self._barrier_round.get(name, 0)
        self._barrier_round[name] = rnd + 1
        arrived = self.add(f"__barrier__/{name}/{rnd}/count", 1)
        flag = f"__barrier__/{name}/{rnd}/done"
        if arrived == world_size:
            self.set(flag, b"1")
        else:
            self.get(flag, timeout_ms=timeout_ms)

    def close(self) -> None:
        if self._h:
            self._lib.tpustore_disconnect(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# Data generation
# ---------------------------------------------------------------------------

def gen_templates(seed: int, num_classes: int, shape: tuple[int, ...],
                  *, threads: int = 0) -> np.ndarray:
    lib = load_library()
    elems = int(np.prod(shape))
    out = np.empty((num_classes, elems), np.float32)
    lib.datagen_templates(
        seed, num_classes, elems,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        threads or _default_threads(),
    )
    return out.reshape((num_classes, *shape))


def gen_images(seed: int, step: int, batch: int, templates: np.ndarray,
               noise: float, *, threads: int = 0
               ) -> tuple[np.ndarray, np.ndarray]:
    lib = load_library()
    templates = np.ascontiguousarray(templates, np.float32)
    num_classes = templates.shape[0]
    shape = templates.shape[1:]
    elems = int(np.prod(shape))
    x = np.empty((batch, elems), np.float32)
    y = np.empty((batch,), np.int32)
    lib.datagen_images(
        seed, step, batch, elems, num_classes, noise,
        templates.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        y.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        threads or _default_threads(),
    )
    return x.reshape((batch, *shape)), y


def gen_lm(seed: int, step: int, batch: int, seq_len: int, vocab: int,
           a: int, c: int, noise_frac: float, *, threads: int = 0
           ) -> np.ndarray:
    """Returns (batch, seq_len+1) int32 tokens."""
    lib = load_library()
    out = np.empty((batch, seq_len + 1), np.int32)
    lib.datagen_lm(
        seed, step, batch, seq_len, vocab, a, c, noise_frac,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        threads or _default_threads(),
    )
    return out


def _default_threads() -> int:
    import os

    return min(8, os.cpu_count() or 1)
