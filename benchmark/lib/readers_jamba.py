"""The readers of ``jamba2_3b`` (``program.readers``).

The counters are the program's own (``obs``' registry, summed on the
device over real tokens and published by the engine every 64 decode
rounds): ``ssm_calls_total{kind,layer}`` (executions of a Mamba layer)
and ``ssm_tokens_total{kind,layer}`` (real positions that advanced its
state). They run from the process's first request: warm-up, the closed
loop's fill, the window and its drain; the mean of active rows a round
is a ratio of the two, so the longer span moves it only as far as those
phases differ from the window. A program without the counters gives
``None`` everywhere.
"""

from __future__ import annotations

from benchmark.lib import costs_jamba, readers
from benchmark.lib.common import log
from benchmark.lib.readers_kexaone import counters


def active_rows_per_round():
    """Sequences a decode round advanced, in the mean: the state-space
    layers' tokens over their executions, kind decode."""
    c = counters("ssm_", "decode")
    if not c.get("ssm_calls_total"):
        return None
    return c.get("ssm_tokens_total", 0.0) / c["ssm_calls_total"]


def _round_bytes(run: dict):
    """``(all bytes, state bytes)`` a decode round of the window had to
    move, or None."""
    rounds = len(run["round_seconds"])
    active = active_rows_per_round()
    if not rounds or active is None:
        return None
    cfg = run["cfg"]
    attended = sum(d for _, d in readers.decode_tokens_in_window(run)) \
        / rounds
    return (costs_jamba.decode_round_bytes(cfg, active, attended),
            costs_jamba.decode_round_state_bytes(cfg, active))


def decode_hbm_share_pct(run: dict):
    """Bytes the traced decode rounds had to move over their device time
    at the chip's peak bandwidth: ``costs_jamba.decode_round_bytes`` with
    the counters' mean of active rows a round and the window's mean of
    rows attended a round in the attention layers."""
    mod = readers._module(run, r"serve_step")
    need = _round_bytes(run)
    if mod is None or need is None:
        return None
    n, secs = mod
    log(f"state-space counters, decode: {active_rows_per_round():.2f} "
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
    the chip's peak, matrix products only (the scan's time is in the
    denominator and its operations are not in the numerator): each
    traced execution is charged ``costs_jamba.prefill_flops`` of its
    own span's ``tokens`` (``readers.prefill_flops_share``)."""
    return readers.prefill_flops_share(
        run, lambda t, _: costs_jamba.prefill_flops(run["cfg"], t))
