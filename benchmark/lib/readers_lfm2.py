"""Readers of the ``.lfm2`` metrics that no other cell has.

The counters are the program's own (``obs``' registry, summed on the
device over real tokens and published by the engine every 64 decode
rounds): by layer and program kind ``conv_calls_total`` and
``conv_tokens_total`` (executions of a short-convolution layer and the
real positions that moved its carried inputs), the routing counts
``moe_*_total`` of the twelve expert layers and the attention rows
``attn_rows_*_total{attn="full"}`` of the three attention layers. They
run from the process's first request: warm-up, the closed loop's fill,
the window and its drain; every metric here is a ratio of two of them,
so the longer span moves it only as far as those phases differ from the
window. A program without the counters (the parent of the PR that
brought them cannot build the model at all) gives ``None`` everywhere.
"""

from __future__ import annotations

import functools

from benchmark.lib import costs_lfm2, host_spans, readers, readers_axk1
from benchmark.lib import trace_reduce as tr
from benchmark.lib.common import log
from benchmark.lib.readers_kexaone import counters


def _routing(kind: str):
    c = counters("moe_", kind)
    return c if c.get("moe_calls_total") else None


def _rounds():
    """``(rounds the counters saw, active rows a round in the mean)``:
    a convolution layer's executions of kind decode, and the real
    positions over them."""
    c = counters("conv_", "decode")
    if not c.get("conv_calls_total"):
        return None
    return c["conv_calls_total"], \
        c.get("conv_tokens_total", 0.0) / c["conv_calls_total"]


def held_experts_touched_share_pct(run: dict):
    c = _routing("decode")
    if c is None:
        return None
    return 100.0 * c.get("moe_held_experts_touched_total", 0.0) \
        / c["moe_calls_total"] / run["cfg"]["num_experts"]


def cache_rows_attended_share_pct(run: dict):
    """In the attention layers, cache rows inside the mask over cache
    rows the decode rounds scored (a row's whole padded length)."""
    del run
    c = counters("attn_", "decode")
    if not c.get("attn_rows_read_total"):
        return None
    return 100.0 * c.get("attn_rows_attended_total", 0.0) \
        / c["attn_rows_read_total"]


def _round_need(run: dict):
    """``(experts touched a round summed over the layers, active rows a
    round, rows attended a round in one attention layer)`` in the mean
    over the rounds the counters saw, or None."""
    c, r = _routing("decode"), _rounds()
    a = counters("attn_", "decode")
    if c is None or r is None or not a.get("attn_rows_attended_total"):
        return None
    n = costs_lfm2.layer_counts(run["cfg"])
    layer_rounds, active = r
    touched = c.get("moe_held_experts_touched_total", 0.0) \
        / c["moe_calls_total"] * n["sparse"]
    attended = a["attn_rows_attended_total"] / n["attn"] \
        / (layer_rounds / n["conv"])
    return touched, active, attended


def decode_hbm_share_pct(run: dict):
    """Bytes the traced decode rounds had to move over their device time
    at the chip's peak bandwidth: ``costs_lfm2.decode_round_bytes`` with
    the counters' means a round of held experts touched (summed over the
    layers), active rows and rows attended."""
    mod = readers._module(run, r"serve_step")
    need = _round_need(run)
    if mod is None or need is None:
        return None
    n, secs = mod
    cfg = run["cfg"]
    per_round = costs_lfm2.decode_round_bytes(cfg, *need)
    log(f"a round, by the counters: {need[0]:.1f} experts touched over "
        f"the layers, {need[1]:.2f} active rows of {run['slots']}, "
        f"{need[2]:.0f} rows attended a layer; it must move "
        f"{per_round / 1e9:.3f} GB, "
        f"{costs_lfm2.experts_bytes(cfg, need[0]) / 1e9:.3f} of them "
        f"experts; {n} traced rounds in {secs:.3f} s")
    return 100.0 * n * per_round / (secs * run["peaks"]["hbm_bytes_per_s"])


def state_bytes_share_pct(run: dict):
    """Of the bytes a decode round must move, the share that is the
    convolutions' carried inputs."""
    need = _round_need(run)
    if need is None:
        return None
    return 100.0 * costs_lfm2.decode_round_state_bytes(run["cfg"], need[1]) \
        / costs_lfm2.decode_round_bytes(run["cfg"], *need)


@functools.lru_cache(maxsize=1)
def _chip0(path: str) -> dict:
    devs = tr.load(path)
    return devs[min(devs)]


def _trace(run: dict):
    """Chip 0's traced events, read once for the readers that want
    them, or None for a run without a trace."""
    if run.get("trace") is None:
        return None
    return _chip0(tr.find_xplane(str(host_spans.ROOT / ".bench_trace"
                                     / run["workload"])))


def grouped_experts_hbm_share_pct(run: dict):
    """The kernel's own roofline in the decode round: the bytes of the
    experts the traced rounds touched (by the counters' mean, at
    ``costs_lfm2.experts_bytes``) over the device time of the
    ``grouped_experts`` operations that ran inside a ``serve_step``
    execution on chip 0, at the chip's peak bandwidth. At eight rows an
    expert the kernel is bound by the experts' bytes."""
    need = _round_need(run)
    dev = _trace(run)
    if need is None or dev is None:
        return None
    steps = [(s, e) for n, s, e in dev["modules"] if "serve_step" in n]
    inside = tr.total(host_spans.intersect(
        tr.union(steps), tr.union([(s, e) for n, s, e in dev["ops"]
                                   if n == "grouped_experts"]))) / 1e9
    if not steps or not inside:
        return None
    total = sum(e - s for s, e in steps) / 1e9
    log(f"grouped_experts inside {len(steps)} traced rounds: {inside:.3f} "
        f"s of their {total:.3f} s")
    return 100.0 * len(steps) * costs_lfm2.experts_bytes(
        run["cfg"], need[0]) / (inside * run["peaks"]["hbm_bytes_per_s"])


def prefill_flops_share_pct(run: dict):
    """Operations the traced prefills needed over their device time at
    the chip's peak, matrix products only. Each ``serve_prefill``
    execution on chip 0 is paired with the ``serve/prefill_into`` span
    that holds its midpoint and needs ``costs_lfm2.prefill_flops`` of
    that span's ``tokens`` with the counters' mean pairs a token a
    sparse layer (as ``readers_axk1.prefill_flops_share_pct`` pairs
    them); an execution whose span began before the session is left out,
    time and all."""
    into = readers_axk1._prefill_spans(run)
    c = _routing("prefill")
    dev = _trace(run)
    if into is None or c is None or dev is None:
        return None
    cfg = run["cfg"]
    pairs = c.get("moe_held_pairs_total", 0.0) \
        / (c["moe_picks_total"] / cfg["num_experts_per_tok"])
    need = secs = 0.0
    paired = 0
    execs = [(s, e) for n, s, e in dev["modules"] if "serve_prefill" in n]
    for s, e in execs:
        mid = 0.5 * (s + e)
        span = next((sp for sp in into if sp[0] <= mid <= sp[1]), None)
        if span is None:
            continue
        paired += 1
        need += costs_lfm2.prefill_flops(cfg, span[2], pairs)
        secs += (e - s) / 1e9
    if not secs:
        return None
    log(f"traced prefills paired with their spans: {paired} of "
        f"{len(execs)} executions, {secs:.3f} s; {pairs:.3f} pairs a "
        f"token a sparse layer on held experts")
    return 100.0 * need / (secs * run["peaks"]["bf16_flops"])
