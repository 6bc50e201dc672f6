"""Serving cells: the program's server under a pinned schedule.

The system under test is built the way ``scripts/serve.py`` builds it:
``get_config`` + ``get_model``, a ``ServingEngine`` with the script's
defaults (block 16, queue 64, two prefills a round, prefix cache on)
behind an ``InferenceServer``; slots and positions come from the
traffic file as they come from the script's ``--slots`` and
``--max-seq-len``. Requests go in through ``server.stream`` and every
token is stamped when the client's own thread takes it off the stream.

What the benchmark adds: weights from ``--seed`` made on the device
(``weights.py``), the pinned schedule and its token ids
(``traffic.py``), the open-loop replayer that times from when a request
was *due*, and the closed-loop callers.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

from benchmark.lib import traffic as traffic_lib
from benchmark.lib import weights
from benchmark.lib.common import log

_SERVE_DEFAULTS = dict(block_size=16, max_queue=64,
                       max_prefills_per_round=2)  # scripts/serve.py's


@dataclasses.dataclass
class Sent:
    """One request as the client saw it (times: ``time.monotonic()``)."""

    rec: dict
    prompt: np.ndarray
    due: float = 0.0
    sent: float = 0.0
    request: object = None
    arrivals: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    closed: float = 0.0
    client: int = -1

    @property
    def ok(self) -> bool:
        return (self.closed > 0.0 and self.request is not None
                and self.request.state == "done"
                and len(self.tokens) == int(self.rec["max_new"]))


def program_model(cfg: dict):
    """The program's model object for a decoder configuration."""
    from pytorch_distributed_nn_tpu.config import get_config
    from pytorch_distributed_nn_tpu.models import get_model

    prog = cfg["program"]
    pc = get_config(prog["preset"])
    pc.model.name = prog["model_name"]
    pc.model.remat = False
    pc.model.dtype = cfg["torch_dtype"]
    pc.model.extra = dict(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        mlp_dim=cfg["intermediate_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"])
    return get_model(pc.model)


def build(cfg: dict, ref, traf: dict, seed: int, phases):
    """(model, engine, server), weights on the device, loop started."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.serve import (
        InferenceServer,
        ServingEngine,
    )

    model = program_model(cfg)
    spec = ref.param_spec(cfg)
    params = weights.tree(seed, spec)
    jax.block_until_ready(params)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                           train=False))["params"]
    weights.check_layout(params, shapes)
    phases.close("weights")
    srv = traf["server"]
    engine = ServingEngine(model, params, max_slots=int(srv["slots"]),
                           max_seq_len=int(srv["max_seq_len"]),
                           **_SERVE_DEFAULTS)
    server = InferenceServer(engine).start()
    phases.close("engine")
    return model, engine, server


def flat_schedule(sched, kind: str) -> list:
    """Every request of a schedule, open loop or closed."""
    return sched if kind == "serve_open" else [r for c in sched for r in c]


def warm_up(server, sched_flat: list, vocab: int, seed: int) -> None:
    """One request for each power-of-two class of prompt length the
    schedule holds, at the class's longest prompt, two tokens each (the
    second forces a decode round): every program the window drives is
    then compiled or loaded, and ``window_compiles`` says if not."""
    classes: dict = {}
    for r in sched_flat:
        c = max(16, 1 << (int(r["prompt_len"]) - 1).bit_length())
        classes[c] = max(classes.get(c, 0), int(r["prompt_len"]))
    rng = np.random.default_rng([int(seed), 3])
    for c in sorted(classes):
        prompt = rng.integers(0, vocab, size=(classes[c],)).astype(np.int32)
        req = server.generate(prompt, 2, timeout=1500.0)
        if req.state != "done":
            raise RuntimeError(f"warm-up request of {classes[c]} tokens "
                               f"ended {req.state} {req.reject_reason}")
    log(f"warmed prompt classes {sorted(classes)} (longest each: "
        f"{[classes[c] for c in sorted(classes)]})")


def _consume(s: Sent, stream) -> None:
    for chunk in stream:
        now = time.monotonic()
        for tok in chunk:
            s.arrivals.append(now)
            s.tokens.append(int(tok))
    s.closed = time.monotonic()


def _send(server, s: Sent):
    s.sent = time.monotonic()
    stream = server.stream(s.prompt, int(s.rec["max_new"]))
    s.request = stream.request
    return stream


def open_loop(server, sent: list, t0: float, consumers: list) -> None:
    """The replayer: sleeps to each offset, sends, and hands the stream
    to a thread of its own (the client reading its tokens)."""
    for s in sent:
        s.due = t0 + float(s.rec["t"])
        wait = s.due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        stream = _send(server, s)
        th = threading.Thread(target=_consume, args=(s, stream),
                              name=f"client-{s.rec['i']}", daemon=True)
        th.start()
        consumers.append(th)


def closed_loop_client(server, mine: list, client: int, stop, out: list,
                       lock, prompt_of) -> None:
    """One caller: next request when the last one's final token came,
    walking its own pinned list and cycling when it ends."""
    k = 0
    while not stop.is_set():
        rec = mine[k % len(mine)]
        k += 1
        s = Sent(rec=rec, prompt=prompt_of(rec), client=client)
        s.due = time.monotonic()
        stream = _send(server, s)
        with lock:
            out.append(s)
        _consume(s, stream)


def run_window(server, engine, traf: dict, sched, vocab: int, seed: int,
               seconds: float, phases, tracer=None) -> dict:
    """Drive the window. Returns the run's records: every request with
    its stamps, the engine's rounds inside the window, the window's
    bounds and (closed loop) what happened before it opened."""
    kind = traf["kind"]
    flat = flat_schedule(sched, kind)
    prompts = {int(r["i"]): traffic_lib.prompt_tokens(r, seed, vocab)
               for r in flat}
    phases.close("schedule_and_tokens")
    rounds0 = len(engine.round_seconds)
    deadline = seconds + float(traf.get("drain_timeout_s", 120.0))
    if kind == "serve_open":
        sent = [Sent(rec=r, prompt=prompts[int(r["i"])]) for r in flat]
        consumers: list = []
        setup_done = time.perf_counter()
        t0 = time.monotonic()
        rep = threading.Thread(target=open_loop,
                               args=(server, sent, t0, consumers),
                               name="replayer", daemon=True)
        rep.start()
        if tracer is not None:
            tracer.start_in_background(t0, seconds)
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        t1 = time.monotonic()
        rounds1 = len(engine.round_seconds)
        rep.join(deadline)
        for th in consumers:   # the drain: outside window and set-up
            th.join(max(0.0, t0 + deadline - time.monotonic()))
        pre = []
    else:
        stop, lock, sent_all = threading.Event(), threading.Lock(), []
        clients = [threading.Thread(
            target=closed_loop_client,
            args=(server, mine, c, stop, sent_all, lock,
                  lambda r: prompts[int(r["i"])]),
            name=f"caller-{c}", daemon=True)
            for c, mine in enumerate(sched)]
        for th in clients:
            th.start()
        # the window opens once every caller has finished one request
        t_give_up = time.monotonic() + 600.0
        while True:
            with lock:
                done = {s.client for s in sent_all if s.closed > 0.0}
            if len(done) == len(clients):
                break
            if time.monotonic() > t_give_up:
                raise RuntimeError("callers did not each finish a request")
            time.sleep(0.01)
        phases.close("closed_loop_fill")
        rounds0 = len(engine.round_seconds)
        setup_done = time.perf_counter()
        t0 = time.monotonic()
        if tracer is not None:
            tracer.start_in_background(t0, seconds)
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        t1 = time.monotonic()
        rounds1 = len(engine.round_seconds)
        stop.set()
        for th in clients:
            th.join(max(0.0, t0 + deadline - time.monotonic()))
        with lock:
            sent = list(sent_all)
        pre = [s for s in sent if s.closed and s.closed <= t0]
    return dict(kind=kind, sent=sent, before_window=pre, t0=t0, t1=t1,
                setup_done=setup_done,
                round_seconds=list(engine.round_seconds[rounds0:rounds1]),
                slots=int(traf["server"]["slots"]))
