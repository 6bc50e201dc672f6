"""What an entry point settles before its first backend use.

Platform and virtual-device count come from the environment and need no
code: ``JAX_PLATFORMS=cpu`` with ``JAX_NUM_CPU_DEVICES=8`` for the CPU
test route, nothing for the chip. Three things do need code:

- :func:`configure_compile_cache` — where JAX's persistent compilation
  cache lives. Every entry point calls it first;
- :func:`require_tpu` — the device check of ``chip_smoke.py``: no chip
  is an error, never a CPU number;
- :func:`claim_chip` — a chip belongs to one process at a time, so a
  second process that wants it on the same host fails with a message
  instead of waiting inside backend initialisation.
"""

from __future__ import annotations

import fcntl
import os
import tempfile
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = Path(__file__).resolve().parents[2]


def configure_compile_cache() -> str | None:
    """Place the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself and this
    sets nothing. Otherwise the cache is ``<checkout>/.jax_cache`` — a
    fixed path, because the path is part of the cache key and a
    directory that moves never hits."""
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          str(_CHECKOUT / ".jax_cache"))
    _key_the_scopes()
    return jax.config.jax_compilation_cache_dir


def _key_the_scopes() -> None:
    """Put ``obs.scopes.VERSION`` into the cache's key. JAX leaves an
    instruction's metadata out of the key, so an executable cached
    before a named scope was added is found again and loaded with the
    old ``op_name``s, and the map from instruction to scope
    (``obs/scopes.py``) would read those. With the version in the key a
    tree whose scopes changed compiles once more, cold, and then finds
    its own entries. ``cache_key.custom_hook`` is JAX's own place for a
    string of the deployment's; where a JAX has none the key stays as
    it was."""
    from pytorch_distributed_nn_tpu.obs import scopes

    try:
        from jax._src import cache_key
    except ImportError:
        return
    if hasattr(cache_key, "custom_hook"):
        cache_key.custom_hook = lambda: scopes.VERSION


def _cpu_asked_for() -> bool:
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def require_tpu() -> jax.Device:
    """``jax.devices()[0]``, which must be a TPU: a CPU is refused
    whether ``JAX_PLATFORMS`` asked for it or JAX fell back to it."""
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return dev
    raise RuntimeError(
        f"no TPU: jax.devices()[0] is {dev.platform}:{dev.device_kind} "
        f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). This "
        "entry point measures the chip and does not fall back to the "
        "CPU.")


def claim_chip(lock_dir: str | None = None) -> int | None:
    """Take this host's chip for the life of the process, or exit
    saying who holds it. Returns the open lock descriptor (None when
    ``JAX_PLATFORMS=cpu``: nothing to claim).

    One process drives every chip of a host unless ``TPU_VISIBLE_CHIPS``
    hands it a subset, so the lock is per host and per that subset.
    Held through an open ``flock``: the kernel releases it when the
    process ends, however it ends."""
    if _cpu_asked_for():
        return None
    chips = os.environ.get("TPU_VISIBLE_CHIPS", "all").replace(",", "_")
    path = os.path.join(lock_dir or tempfile.gettempdir(),
                        f"tpunn-chip-{chips}.lock")
    fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o666)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
    except BlockingIOError:
        holder = os.read(fd, 32).decode(errors="replace").strip()
        os.close(fd)
        raise SystemExit(
            f"the TPU of this host (chips: {chips}) is held by pid "
            f"{holder or '?'}: a chip belongs to one process at a time, "
            "and a second process would wait for it inside backend "
            "initialisation. Run one chip-backed worker per host (one "
            "process drives all its chips), give each worker its own "
            "TPU_VISIBLE_CHIPS, or set JAX_PLATFORMS=cpu.") from None
    os.ftruncate(fd, 0)
    os.write(fd, str(os.getpid()).encode())
    return fd
