"""Device table, compile meter, percentiles and file lookup."""

from __future__ import annotations

import importlib.util
import json
import math
import sys
import time
from pathlib import Path

# Published peaks of one chip, keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s). A device that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": dict(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                        hbm_bytes=16e9),
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(f"benchmark: no published peaks for device kind "
                         f"{device_kind!r}; add it to benchmark/lib/"
                         f"common.py with its source")
    return PEAKS[device_kind]


def log(msg: str) -> None:
    """Progress goes to stdout on earlier lines (the result is the last
    line); flushed so a killed run still shows how far it got."""
    print(f"[bench] {msg}", flush=True)


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one file of the benchmark by path (metric readers have
    dots in their names, references sit beside their configuration)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def percentile(values, q: float) -> float:
    """Nearest-rank percentile, q in (0, 100]: the value below which at
    least q % of the sample lies. No interpolation, so every reading is
    a time some request really saw."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


class Phases:
    """Set-up phase seconds, printed as they close."""

    def __init__(self, t0: float) -> None:
        self.t0 = t0
        self.last = t0
        self.seconds: dict[str, float] = {}

    def close(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self.last
        self.last = now
        log(f"setup phase {name}: {self.seconds[name]:.2f} s "
            f"(at {now - self.t0:.2f} s)")


class CompileMeter:
    """Backend compile events and persistent-cache traffic from
    ``jax.monitoring`` (copied from chip_smoke.py's Meter). A cache hit
    still fires the compile-duration event (it times the load), so
    compiles inside a window are counted by cache misses plus programs
    below the cache's size floor: both are 'a program was built now'."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name: str, secs: float, **_) -> None:
        if name == self._COMPILE:
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, name: str, **_) -> None:
        if name == self._HIT:
            self.hits += 1
        elif name == self._MISS:
            self.misses += 1

    def mark(self) -> tuple:
        return (self.compiles, self.compile_s, self.hits, self.misses)

    def since(self, mark: tuple) -> dict:
        c, s, h, m = mark
        return dict(compiles=self.compiles - c,
                    compile_s=self.compile_s - s,
                    cache_hits=self.hits - h, cache_misses=self.misses - m)


def device_facts(devices) -> dict:
    """The result line's ``device``: as JAX reports it, peak on the
    fullest chip. The TPU allocator counts live buffers
    (``peak_bytes_in_use``) apart from what loaded programs reserve for
    their temporaries (``peak_bytes_reserved``: 6.5 GB for the BERT step
    at batch 128, seen on the chip in PR 25); memory in use is their
    sum, and that is what is reported."""
    peak = 0
    limit = 0
    for d in devices:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0))
                   + int(ms.get("peak_bytes_reserved", 0)))
        limit = max(limit, int(ms.get("bytes_limit", 0)))
    d0 = devices[0]
    return dict(platform=d0.platform, kind=d0.device_kind,
                count=len(devices), memory_peak_bytes=peak,
                memory_limit_bytes=limit)


def place_compile_cache() -> str:
    """JAX's persistent cache inside the checkout at the fixed path the
    program itself picks (``runtime/device.configure_compile_cache``:
    ``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``),
    with the size and time floors off so that every program of a cell
    is found again by the cell's next run."""
    import jax

    from pytorch_distributed_nn_tpu.runtime.device import (
        configure_compile_cache,
    )

    d = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d
