"""Structured metrics logging with rank-0 aggregation.

The reference prints loss/throughput with bare ``print`` on every rank
(SURVEY.md §5 "Metrics/logging" row). Here: a per-host structured JSONL
writer where only the coordinator (process 0) emits by default — the
analogue of the ``if rank == 0: print`` idiom, but machine-readable and
in the BASELINE.json metric schema so benchmark runs can fill
``published`` directly.
"""

from __future__ import annotations

import json
import logging
import sys
import time
from pathlib import Path
from typing import Any, IO

import jax

log = logging.getLogger(__name__)


class MetricsLogger:
    """JSONL metric stream: one dict per event.

    ``all_hosts=False`` (default) silences non-coordinator processes —
    call sites never need the ``if rank == 0`` guard.
    """

    def __init__(self, path: str | Path | None = None, *,
                 all_hosts: bool = False,
                 stream: IO | None = None) -> None:
        self.enabled = all_hosts or jax.process_index() == 0
        self._fh: IO | None = None
        if not self.enabled:
            return
        if path is not None:
            p = Path(path)
            p.parent.mkdir(parents=True, exist_ok=True)
            self._fh = p.open("a")
        else:
            self._fh = stream or sys.stdout

    def emit(self, event: str, **fields: Any) -> None:
        if not self.enabled:
            return
        rec = {"event": event, "time": time.time(),
               "process": jax.process_index(), **fields}
        self._fh.write(json.dumps(rec) + "\n")
        self._fh.flush()

    def close(self) -> None:
        if self._fh is not None and self._fh not in (sys.stdout,
                                                     sys.stderr):
            self._fh.close()
            self._fh = None  # idempotent: double-close is a no-op
            self.enabled = False  # emit after close: silent no-op

    # context manager: `with MetricsLogger(path) as m:` guarantees the
    # file handle closes on exceptions (Trainer rides this via its own
    # __enter__/__exit__)
    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
