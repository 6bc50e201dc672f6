"""What the metric readers share: each reads one quantity off a run.

A run is the dictionary ``run.run_cell`` returns: the window's bounds
(``t0``, ``t1``, ``time.monotonic()``), for serving every request as the
client saw it (``sent``: due, sent, each token's arrival) and the
engine's round times, for training the step count and the trainer's
goodput readings, the compile events inside the window, the device
facts, and, in a traced run, the reduced trace (``trace``).
"""

from __future__ import annotations

import functools
import importlib
import re
import statistics

from benchmark.lib import costs, host_spans
from benchmark.lib import trace_reduce as tr
from benchmark.lib.common import log, percentile


def window_s(run: dict) -> float:
    return run["t1"] - run["t0"]


# -- the model's own readers ------------------------------------------------

def for_run(run: dict):
    """The module under ``benchmark/lib/`` that holds the readers of the
    run's model, as its configuration names it (``program.readers``);
    this module for a configuration that names none. A metric several
    models have is one file under ``metrics/`` that asks here, so no
    metric file names a model or a cell."""
    name = run["cfg"].get("program", {}).get("readers", "readers")
    return importlib.import_module(f"benchmark.lib.{name}")


def of_model(run: dict, reader: str, fallback=None):
    """What the model's ``reader`` says of the run; ``fallback``'s word
    where the model's module has no such function, and nothing where
    there is neither: a reader that finds nothing says nothing."""
    fn = getattr(for_run(run), reader, fallback)
    return None if fn is None else fn(run)


# -- serving --------------------------------------------------------------

def ttfts_ms(run: dict) -> list:
    """First token minus due, for every request due in the window; one
    that was refused or failed counts as the largest value seen (or as
    the whole window, if none came back)."""
    sent = run["sent"]
    good = [(s.arrivals[0] - s.due) * 1e3 for s in sent if s.arrivals]
    worst = max(good, default=window_s(run) * 1e3)
    return good + [worst] * (len(sent) - len(good))


def itls_ms(run: dict) -> list:
    """Gaps between consecutive tokens of one request, as its client
    took them off the stream, over all requests due in the window."""
    return [(b - a) * 1e3 for s in run["sent"]
            for a, b in zip(s.arrivals, s.arrivals[1:])]


def tokens_in_window(run: dict) -> int:
    """Prompt tokens, counted when the request's first token arrives,
    plus output tokens, counted as each arrives, inside the window.
    A prompt is ~0.8 % of a window's tokens, so the total moves in steps
    of that size with the side of the window's edge on which one first
    token falls; PERF.md (Findings, PR 25) says why that was kept."""
    t0, t1 = run["t0"], run["t1"]
    n = 0
    for s in run["sent"]:
        if s.arrivals and t0 <= s.arrivals[0] <= t1:
            n += len(s.prompt)
        n += sum(1 for a in s.arrivals if t0 <= a <= t1)
    return n


def queue_waits_ms(run: dict) -> list:
    return [(s.request.t_admit - s.due) * 1e3 for s in run["sent"]
            if s.request is not None and s.request.t_admit > 0.0]


def generator_late_ms(run: dict) -> list:
    return [(s.sent - s.due) * 1e3 for s in run["sent"] if s.sent > 0.0]


def decode_tokens_in_window(run: dict) -> list:
    """``(arrival, depth)`` of every token a decode round produced
    inside the window: token k >= 1 of a request attends over
    ``prompt_len + k`` positions (token 0 comes out of the prefill)."""
    t0, t1 = run["t0"], run["t1"]
    return [(a, len(s.prompt) + k) for s in run["sent"]
            for k, a in enumerate(s.arrivals) if k >= 1 and t0 <= a <= t1]


def batch_occupancy_pct(run: dict):
    rounds = len(run["round_seconds"])
    if not rounds:
        return None
    return 100.0 * len(decode_tokens_in_window(run)) / (rounds * run["slots"])


def decode_round_p50_ms(run: dict):
    rs = run["round_seconds"]
    return statistics.median(rs) * 1e3 if rs else None


def prefill_share_pct(run: dict):
    """Engine wall between admitting a request and its first token,
    summed over the requests admitted in the window, over the window."""
    t0, t1 = run["t0"], run["t1"]
    wall = sum(s.request.t_first_token - s.request.t_admit
               for s in run["sent"] if s.request is not None
               and s.request.t_first_token > 0.0
               and t0 <= s.request.t_admit <= t1)
    return 100.0 * wall / window_s(run)


def _module(run: dict, pattern: str):
    """(executions, device seconds) of the traced programs whose name
    matches, or None."""
    if run.get("trace") is None:
        return None
    n, t = 0, 0.0
    for name, (k, secs) in run["trace"]["modules"].items():
        if re.search(pattern, name):
            n, t = n + k, t + secs
    return (n, t) if n else None


def decode_hbm_share_pct(run: dict):
    """Bytes the traced decode rounds had to read, over their device
    time at the chip's peak bandwidth. Per round: every layer's
    matrices and the LM head once, plus the key and value rows of the
    positions the round's tokens attend over (the window's mean rows a
    round; the padded rest of the cache is not counted)."""
    mod = _module(run, r"serve_step")
    rounds = len(run["round_seconds"])
    if mod is None or not rounds:
        return None
    n, secs = mod
    rows = sum(d for _, d in decode_tokens_in_window(run)) / rounds
    cfg = run["cfg"]
    need = n * (costs.decoder_round_weight_bytes(cfg)
                + rows * costs.decoder_kv_bytes_per_position(cfg))
    return 100.0 * need / (secs * run["peaks"]["hbm_bytes_per_s"])


@functools.lru_cache(maxsize=1)
def _chip0(path: str) -> dict:
    devs = tr.load(path)
    return devs[min(devs)]


def chip0_events(run: dict):
    """Chip 0's traced programs and operations (``modules``, ``ops``:
    ``[(name, start_ns, end_ns)]``), read once for the readers that want
    them, or None for a run without a trace."""
    if run.get("trace") is None:
        return None
    try:
        return _chip0(tr.find_xplane(str(host_spans.ROOT / ".bench_trace"
                                         / run["workload"])))
    except FileNotFoundError:
        return None


def prefill_spans(run: dict):
    """``[(start, end, tokens, padded, cached)]`` of the traced
    ``serve/prefill_into`` spans, or None."""
    a = host_spans.of_run(run)
    if a is None:
        return None
    into = [(s, e, int(st["tokens"]), int(st["padded"]), int(st["cached"]))
            for n, s, e, st in a["spans"] if n == "serve/prefill_into"
            and "cached" in st]
    return into or None


def paired_prefills(run: dict):
    """``[(device seconds, tokens, padded, cached)]``: each
    ``serve_prefill`` execution on chip 0 with the ``serve/prefill_into``
    span that holds its midpoint (the span waits for the execution's
    token), so a share charges the traced prefills their own work and
    not the window's mean; an execution whose span began before the
    session is left out, time and all. None where there is no trace, no
    span or no pair."""
    into, dev = prefill_spans(run), chip0_events(run)
    if into is None or dev is None:
        return None
    execs = [(s, e) for n, s, e in dev["modules"] if "serve_prefill" in n]
    out = []
    for s, e in execs:
        mid = 0.5 * (s + e)
        span = next((sp for sp in into if sp[0] <= mid <= sp[1]), None)
        if span is not None:
            out.append(((e - s) / 1e9,) + span[2:])
    if not out:
        return None
    behind = [p for p in out if p[3]]
    log(f"traced prefills paired with their spans: {len(out)} of "
        f"{len(execs)} executions, {sum(p[0] for p in out):.3f} s, "
        f"{sum(p[1] for p in out)} tokens prefilled; {len(behind)} of "
        f"them behind restored rows, {sum(p[0] for p in behind):.3f} s")
    return out


def prefill_flops_share(run: dict, flops_of):
    """Operations the traced prefills needed over their device time at
    the chip's peak, where one prefill of ``tokens`` behind ``cached``
    restored rows needs ``flops_of(tokens, cached)``: every traced
    execution is charged its own span's work
    (:func:`paired_prefills`)."""
    execs = paired_prefills(run)
    if execs is None:
        return None
    need = sum(flops_of(tokens, cached) for _, tokens, _, cached in execs)
    return 100.0 * need / (sum(x[0] for x in execs)
                           * run["peaks"]["bf16_flops"])


def op_inside_module(run: dict, op: str, module: str):
    """``(executions of the traced programs whose name holds ``module``,
    device seconds of the operations named ``op`` inside them)`` on chip
    0, or None where either is missing."""
    dev = chip0_events(run)
    if dev is None:
        return None
    progs = [(s, e) for n, s, e in dev["modules"] if module in n]
    inside = tr.total(host_spans.intersect(
        tr.union(progs), tr.union((s, e) for n, s, e in dev["ops"]
                                  if n == op))) / 1e9
    if not progs or not inside:
        return None
    log(f"{op} inside {len(progs)} traced {module} executions: "
        f"{inside:.3f} s of their "
        f"{sum(e - s for s, e in progs) / 1e9:.3f} s")
    return len(progs), inside


def prefill_flops_share_pct(run: dict):
    """:func:`prefill_flops_share` at ``costs.decoder_prefill_flops``
    of the ``tokens`` prefilled (scores against restored rows count
    nothing, so the share reads low, never high, behind a prefix
    hit)."""
    return prefill_flops_share(
        run, lambda tokens, _: costs.decoder_prefill_flops(run["cfg"],
                                                           tokens))


# -- training -------------------------------------------------------------

def samples_per_s_per_chip(run: dict) -> float:
    return run["steps"] * run["batch"] / window_s(run) / run["chips"]


def data_wait_share_pct(run: dict):
    after, before = run["goodput_after"], run["goodput_before"]
    waited = after["data_s"] - (before["data_s"] if before else 0.0)
    return 100.0 * waited / window_s(run)


def step_module(run: dict):
    """The traced program with the most device time: the train step."""
    if run.get("trace") is None or not run["trace"]["modules"]:
        return None
    name = max(run["trace"]["modules"],
               key=lambda k: run["trace"]["modules"][k][1])
    return run["trace"]["modules"][name]


def train_mfu_pct(run: dict) -> float:
    cfg, traf = run["cfg"], run["traffic"]
    flops = costs.encoder_train_flops_per_sample(cfg, int(traf["seq_len"]))
    return 100.0 * flops * samples_per_s_per_chip(run) \
        / run["peaks"]["bf16_flops"]


# -- device ---------------------------------------------------------------

def device_idle_share_pct(run: dict):
    tr = run.get("trace")
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def peak_hbm_share_pct(run: dict):
    dev = run["device"]
    if not dev.get("memory_limit_bytes"):
        return None
    return 100.0 * dev["memory_peak_bytes"] / dev["memory_limit_bytes"]


def pct(values: list, q: float):
    return percentile(values, q) if values else None
