"""The readers of ``brumby_14b`` (``program.readers``).

The counters are the program's own (``obs``' registry, summed on the
device over real tokens and published by the engine every 64 decode
rounds): ``retention_calls_total{kind,layer}`` (executions of a
retention layer) and ``retention_tokens_total{kind,layer}`` (real
positions that advanced its state). They run from the process's first
request: warm-up, the closed loop's fill, the window and its drain; the
mean of active rows a round is a ratio of the two, so the longer span
moves it only as far as those phases differ from the window. The ops are
the program's two kernels by their names in a trace, ``retention_step``
inside ``serve_step`` and ``retention_chunk`` inside ``serve_prefill``.
A program without the counters or the ops gives ``None`` everywhere.
"""

from __future__ import annotations

from benchmark.lib import costs_brumby, host_spans, readers
from benchmark.lib import trace_reduce as tr
from benchmark.lib.common import log
from benchmark.lib.readers_kexaone import counters


def active_rows_per_round():
    """Sequences a decode round advanced, in the mean: the retention
    layers' tokens over their executions, kind decode."""
    c = counters("retention_", "decode")
    if not c.get("retention_calls_total"):
        return None
    return c.get("retention_tokens_total", 0.0) / c["retention_calls_total"]


def _round_bytes(run: dict):
    """``(all bytes, state bytes)`` a decode round of the window had to
    move, or None."""
    active = active_rows_per_round()
    if not run["round_seconds"] or active is None:
        return None
    cfg = run["cfg"]
    return (costs_brumby.decode_round_bytes(cfg, active),
            costs_brumby.decode_round_state_bytes(cfg, active))


def decode_hbm_share_pct(run: dict):
    """Bytes the traced decode rounds had to move over their device time
    at the chip's peak bandwidth: ``costs_brumby.decode_round_bytes``
    with the counters' mean of active rows a round."""
    mod = readers._module(run, r"serve_step")
    need = _round_bytes(run)
    if mod is None or need is None:
        return None
    n, secs = mod
    log(f"retention counters, decode: {active_rows_per_round():.2f} "
        f"active rows a round of {run['slots']}; a round must move "
        f"{need[0] / 1e9:.3f} GB, {need[1] / 1e9:.3f} of them state; "
        f"{n} traced rounds in {secs:.3f} s")
    return 100.0 * n * need[0] / (secs * run["peaks"]["hbm_bytes_per_s"])


def state_bytes_share_pct(run: dict):
    """Of the bytes a decode round must move, the share that is
    recurrent state."""
    need = _round_bytes(run)
    return None if need is None else 100.0 * need[1] / need[0]


def prefill_flops_share_pct(run: dict):
    """Operations the traced prefills needed over their device time at
    the chip's peak, the weights' products and the retention's: each
    traced execution is charged ``costs_brumby.prefill_flops`` of its
    own span's ``tokens`` (``readers.prefill_flops_share``)."""
    return readers.prefill_flops_share(
        run, lambda t, _: costs_brumby.prefill_flops(run["cfg"], t))


def retention_step_hbm_share_pct(run: dict):
    """Bytes the traced ``retention_step`` executions had to move (each
    active row's state in and out, every layer, by the counters' mean of
    active rows a round) over their device time inside ``serve_step`` at
    the chip's peak bandwidth."""
    active = active_rows_per_round()
    inside = readers.op_inside_module(run, "retention_step", "serve_step")
    if active is None or inside is None:
        return None
    rounds, secs = inside
    cfg = run["cfg"]
    need = rounds * cfg["num_hidden_layers"] * active \
        * costs_brumby.step_bytes_per_row(cfg)
    return 100.0 * need / (secs * run["peaks"]["hbm_bytes_per_s"])


def retention_chunk_flops_share_pct(run: dict):
    """Operations the traced ``retention_chunk`` executions needed
    (``costs_brumby.retention_flops`` of their prefill's span's real
    ``tokens``, every layer) over their device time at the chip's peak:
    each ``serve_prefill`` execution on chip 0 is paired with the
    ``serve/prefill_into`` span that holds its midpoint, and an
    execution without a span is left out, time and all."""
    into, dev = readers.prefill_spans(run), readers.chip0_events(run)
    if into is None or dev is None:
        return None
    ops = tr.union((s, e) for n, s, e in dev["ops"]
                   if n == "retention_chunk")
    need, secs = 0.0, 0.0
    for s, e in ((s, e) for n, s, e in dev["modules"]
                 if "serve_prefill" in n):
        mid = 0.5 * (s + e)
        span = next((sp for sp in into if sp[0] <= mid <= sp[1]), None)
        if span is None:
            continue
        secs += tr.total(host_spans.intersect([(s, e)], ops)) / 1e9
        need += run["cfg"]["num_hidden_layers"] \
            * costs_brumby.retention_flops(run["cfg"], span[2])
    if not secs:
        return None
    log(f"retention_chunk inside the paired prefills: {secs:.3f} s for "
        f"{need / 1e12:.3f} TFLOP")
    return 100.0 * need / (secs * run["peaks"]["bf16_flops"])
