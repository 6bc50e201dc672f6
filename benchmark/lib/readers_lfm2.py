"""The readers of ``lfm2_8b_a1b`` (``program.readers``).

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

from benchmark.lib import costs_lfm2, readers
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


def conv_rows_per_round(run: dict):
    """Rows whose carried inputs a decode round's short convolutions
    moved, in the mean: how full the rounds ran."""
    del run
    r = _rounds()
    return None if r is None else r[1]


def held_pairs_per_round(run: dict):
    """Token-expert pairs computed on the held experts, a layer a round:
    every expert of a layer is held, so active rows x the picks a
    token."""
    c = _routing("decode")
    if c is None:
        return None
    k = run["cfg"]["num_experts_per_tok"]
    rows = c["moe_picks_total"] / c["moe_calls_total"] / k
    log(f"routing counters, decode: {c['moe_calls_total']:.0f} layer "
        f"executions, {rows:.2f} active rows a round of {run['slots']}, "
        f"{k} picks a token")
    return c.get("moe_held_pairs_total", 0.0) / c["moe_calls_total"]


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


def grouped_experts_hbm_share_pct(run: dict):
    """The kernel's own roofline in the decode round: the bytes of the
    experts the traced rounds touched (by the counters' mean, at
    ``costs_lfm2.experts_bytes``) over the device time of the
    ``grouped_experts`` operations that ran inside a ``serve_step``
    execution on chip 0, at the chip's peak bandwidth. At eight rows an
    expert the kernel is bound by the experts' bytes."""
    need = _round_need(run)
    inside = readers.op_inside_module(run, "grouped_experts", "serve_step")
    if need is None or inside is None:
        return None
    n, secs = inside
    return 100.0 * n * costs_lfm2.experts_bytes(run["cfg"], need[0]) \
        / (secs * run["peaks"]["hbm_bytes_per_s"])


def prefill_flops_share_pct(run: dict):
    """Operations the traced prefills needed over their device time at
    the chip's peak, matrix products only: each traced execution is
    charged ``costs_lfm2.prefill_flops`` of its own span's ``tokens``
    (``readers.prefill_flops_share``), with the counters' mean pairs a token
    a sparse layer."""
    c = _routing("prefill")
    if c is None:
        return None
    cfg = run["cfg"]
    pairs = c.get("moe_held_pairs_total", 0.0) \
        / (c["moe_picks_total"] / cfg["num_experts_per_tok"])
    return readers.prefill_flops_share(
        run, lambda t, _: costs_lfm2.prefill_flops(cfg, t, pairs))
