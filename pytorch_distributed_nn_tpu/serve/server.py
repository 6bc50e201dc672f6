"""Thread-based loopback inference server + synthetic load clients.

The front-end of the serving stack for a single-process deployment (and
for every test): one daemon thread drives :class:`serve.engine
.ServingEngine` rounds, client threads submit through the scheduler's
thread-safe admission path and block on each request's ``done`` event.
No sockets on purpose — the transport is not what this subsystem is
about, and a loopback front-end is what CI can exercise
deterministically under ``JAX_PLATFORMS=cpu``.

Shutdown reuses the PR-3 preemption machinery
(:mod:`runtime.failure`): ``install_sigterm_drain`` arms the SIGTERM
handler (flag-only, flight-ring snapshot), the serve loop polls
``preempt_requested()`` once per round, and on notice it **drains** —
queued requests are rejected (clients unblock with reason
``draining``), in-flight sequences finish their budgets, the loop
exits. ``scripts/serve.py`` then exits ``GRACEFUL_EXIT_CODE`` so an
agent classifies the shutdown exactly like a trainer preemption.

Synthetic clients, both canonical load shapes:

- :func:`open_loop_client` — requests arrive on their own schedule
  (a fixed metronome, or seeded exponential gaps — a true Poisson
  process) regardless of completions: the model of external traffic,
  the one that can actually overload the server;
  richer shapes (diurnal, flash crowds, tenant mixes) live in
  :mod:`serve.traffic`;
- :func:`closed_loop_client` — N users, each submits, waits, repeats:
  arrival rate self-throttles to service rate (latency-measurement
  shape, cannot overload).
"""

from __future__ import annotations

import logging
import random
import threading
import time
from typing import Callable, Optional, Sequence

import numpy as np

from pytorch_distributed_nn_tpu.obs import flight, watchtower
from pytorch_distributed_nn_tpu.runtime import failure
from pytorch_distributed_nn_tpu.serve.engine import ServingEngine
from pytorch_distributed_nn_tpu.serve.scheduler import Request

log = logging.getLogger(__name__)


class InferenceServer:
    """Single-threaded engine driver with a thread-safe submit path."""

    def __init__(self, engine: ServingEngine, *,
                 idle_wait_s: float = 0.002) -> None:
        self.engine = engine
        self.idle_wait_s = idle_wait_s
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._drained = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.preempted = False

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "InferenceServer":
        if self._thread is not None:
            raise RuntimeError("server already started")
        self._thread = threading.Thread(target=self._loop,
                                        name="serve-loop", daemon=True)
        self._thread.start()
        return self

    def _loop(self) -> None:
        flight.record("serve", "server_start")
        # the engine's account of this thread runs from here to the end
        # of the drain: the idle waits are a phase of it
        tally = self.engine.loop
        tally.start()
        while not self._stop.is_set():
            if failure.preempt_requested():
                self.preempted = True
                break
            if self.engine.has_work:
                self.engine.step()
            else:
                # park until a submit wakes us (bounded so stop/SIGTERM
                # polls stay live even with no traffic)
                with tally.phase("parked"):
                    self._wake.wait(self.idle_wait_s)
                self._wake.clear()
        self.engine.drain()
        tally.stop()
        tally.publish()
        self._drained.set()
        flight.record("serve", "server_stop",
                      note="preempt" if self.preempted else "stop")

    def stop(self, timeout: float = 60.0) -> None:
        """Graceful stop: drain and join the loop thread."""
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise TimeoutError("serve loop did not drain in time")
            log.info("serve loop: %s", self.engine.loop.report())

    def join_drained(self, timeout: float = 60.0) -> bool:
        """Block until the loop has drained (SIGTERM path)."""
        return self._drained.wait(timeout)

    # -- client surface ----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, **kw) -> Request:
        req = self.engine.submit(prompt, max_new_tokens, **kw)
        # queue-pressure feed from the CLIENT thread: the watchtower
        # still sees a filling queue even when the engine loop itself
        # is wedged and no more rounds (and round hooks) ever run
        watchtower.on_serve_submit(req.request_id,
                                   self.engine.scheduler.queue_depth,
                                   self.engine.scheduler.max_queue)
        self._wake.set()
        return req

    def generate(self, prompt, max_new_tokens: int,
                 timeout: float = 120.0, **kw) -> Request:
        """Blocking convenience: submit + wait for the terminal state."""
        req = self.submit(prompt, max_new_tokens, **kw)
        if not req.done.wait(timeout):
            raise TimeoutError(f"request {req.request_id} did not "
                               f"finish in {timeout}s")
        return req

    def stream(self, prompt, max_new_tokens: int, **kw):
        """Submit with incremental streaming and return the request's
        :class:`serve.decoding.TokenStream`. The first chunk arriving
        is the client-visible TTFT event; iteration ends when the
        engine retires (or rejects/fails) the request — terminal
        transitions close the stream, so a rejected request yields an
        empty terminated stream, never a hang. The Request rides on
        ``stream.request`` for state/record inspection."""
        req = self.submit(prompt, max_new_tokens, stream=True, **kw)
        req.stream.request = req
        return req.stream


def install_sigterm_drain() -> bool:
    """Arm SIGTERM-as-drain-notice (main thread only). The serve loop
    polls :func:`runtime.failure.preempt_requested` per round and
    drains on notice; the CLI exits ``GRACEFUL_EXIT_CODE``."""
    return failure.install_preemption_handler(force=True)


# ---------------------------------------------------------------------------
# Synthetic load clients
# ---------------------------------------------------------------------------


def ragged_prompt_sampler(vocab_size: int, *, min_len: int = 4,
                          max_len: int = 48, seed: int = 0
                          ) -> Callable[[], np.ndarray]:
    """Deterministic ragged-length prompt stream (the workload shape
    continuous batching wins on: short and long prompts interleaved)."""
    rng = np.random.default_rng(seed)

    def sample() -> np.ndarray:
        n = int(rng.integers(min_len, max_len + 1))
        return rng.integers(0, vocab_size, size=(n,)).astype(np.int32)

    return sample


def arrival_offsets(num_requests: int, rate_hz: float, *,
                    arrival: str = "fixed",
                    seed: int = 0) -> list[float]:
    """The open-loop submit schedule as offsets from t0 — split out so
    a determinism test can assert the schedule itself (same seed →
    identical offsets) without racing wall clocks. ``fixed``: a
    metronome at ``1/rate_hz``. ``poisson``: seeded exponential
    inter-arrival gaps (a true Poisson process of the same mean rate —
    the burstiness real traffic has and the metronome hides)."""
    if rate_hz <= 0:
        raise ValueError(f"rate_hz must be > 0, got {rate_hz}")
    if arrival not in ("fixed", "poisson"):
        raise ValueError(f"arrival must be 'fixed' or 'poisson', "
                         f"got {arrival!r}")
    if arrival == "fixed":
        return [i / rate_hz for i in range(num_requests)]
    rng = random.Random(seed)
    offsets, t = [], 0.0
    for _ in range(num_requests):
        offsets.append(t)
        t += rng.expovariate(rate_hz)
    return offsets


def open_loop_client(server: InferenceServer, *, num_requests: int,
                     rate_hz: float, max_new_tokens: int,
                     prompt_sampler: Callable[[], np.ndarray],
                     deadline_s: Optional[float] = None,
                     arrival: str = "fixed",
                     seed: int = 0) -> list[Request]:
    """Submit ``num_requests`` on an open loop (arrivals do not wait
    for completions). ``arrival="fixed"`` keeps the historical
    metronome clock; ``arrival="poisson"`` draws seeded exponential
    inter-arrival gaps via :func:`arrival_offsets`, so the schedule is
    Poisson in fact — not just "Poisson-ish" — and reproducible per
    seed. Returns every Request — including rejected ones; the caller
    inspects states. Blocks until all terminal."""
    offsets = arrival_offsets(num_requests, rate_hz,
                              arrival=arrival, seed=seed)
    reqs: list[Request] = []
    t0 = time.monotonic()
    for off in offsets:
        wait = t0 + off - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        dl = (time.monotonic() + deadline_s
              ) if deadline_s is not None else None
        reqs.append(server.submit(prompt_sampler(), max_new_tokens,
                                  deadline_s=dl))
    for r in reqs:
        r.done.wait()
    return reqs


def closed_loop_client(server: InferenceServer, *, num_users: int,
                       requests_per_user: int, max_new_tokens: int,
                       prompt_sampler: Callable[[], np.ndarray]
                       ) -> list[Request]:
    """``num_users`` synthetic users, each submit->wait->repeat. The
    closed loop self-throttles to service rate — latency numbers from
    it are uncontended-by-construction (use the open loop to probe
    overload)."""
    out_lock = threading.Lock()
    reqs: list[Request] = []

    def user() -> None:
        for _ in range(requests_per_user):
            with out_lock:
                prompt = prompt_sampler()
            r = server.submit(prompt, max_new_tokens)
            with out_lock:
                reqs.append(r)
            r.done.wait()

    threads = [threading.Thread(target=user, daemon=True)
               for _ in range(num_users)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return reqs


def wait_all(reqs: Sequence[Request], timeout: float = 300.0) -> None:
    deadline = time.monotonic() + timeout
    for r in reqs:
        if not r.done.wait(max(deadline - time.monotonic(), 0.0)):
            raise TimeoutError(f"request {r.request_id} still "
                               f"{r.state} at timeout")
