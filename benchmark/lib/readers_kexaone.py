"""The readers of ``k_exaone_236b`` (``program.readers``).

The counters are the program's own (``obs``' registry, summed on the
device over real tokens and published by the engine every 64 decode
rounds): the routing counts ``moe_*_total{kind,layer}`` and the
attention rows ``attn_rows_*_total{kind,layer,attn}``, ``attn`` being
``window`` (a ring layer) or ``full``. They run from the process's
first request: warm-up, the closed loop's fill, the window and its
drain. Every metric here is a ratio of two of them, so the longer span
moves it only as far as those phases differ from the window. A program
without the counters (the parent of the PR that brought them) gives
``None`` everywhere.
"""

from __future__ import annotations

import re

from benchmark.lib import costs_kexaone, readers
from benchmark.lib.common import log


def counters(family: str, kind: str, **labels) -> dict:
    """``{name: total over the matching series}`` of the counters whose
    name starts with ``family`` (``moe_``, ``attn_``), of one program
    kind (``prefill``, ``decode``) and with the further ``labels``."""
    from pytorch_distributed_nn_tpu import obs

    want = dict(labels, kind=kind)
    out: dict = {}
    for key, value in obs.get_registry().snapshot().items():
        name, _, rest = key.partition("{")
        have = dict(re.findall(r'(\w+)="([^"]*)"', rest))
        if name.startswith(family) \
                and all(have.get(k) == v for k, v in want.items()):
            out[name] = out.get(name, 0.0) + value
    return out


def _routing(kind: str):
    c = counters("moe_", kind)
    return c if c.get("moe_calls_total") else None


def held_pairs_per_round(run: dict):
    c = _routing("decode")
    if c is None:
        return None
    cfg = run["cfg"]
    k = cfg["num_experts_per_tok"]
    pairs = c.get("moe_held_pairs_total", 0.0) / c["moe_calls_total"]
    rows = c["moe_picks_total"] / c["moe_calls_total"] / k
    log(f"routing counters, decode: {c['moe_calls_total']:.0f} layer "
        f"executions, {rows:.2f} active rows a round of {run['slots']}, "
        f"{pairs:.3f} pairs a layer a round on held experts against "
        f"{rows * k / cfg['expert_parallel']['ep_size']:.3f} at an even "
        f"spread")
    return pairs


def held_experts_touched_share_pct(run: dict):
    c = _routing("decode")
    if c is None:
        return None
    return 100.0 * c.get("moe_held_experts_touched_total", 0.0) \
        / c["moe_calls_total"] / run["cfg"]["num_experts"]


def full_rows_attended_share_pct(run: dict):
    """In the full layers, cache rows inside the mask over cache rows
    the decode rounds scored."""
    del run
    c = counters("attn_", "decode", attn="full")
    if not c.get("attn_rows_read_total"):
        return None
    return 100.0 * c.get("attn_rows_attended_total", 0.0) \
        / c["attn_rows_read_total"]


def ring_read_share_pct(run: dict):
    """Of all cache rows the decode rounds scored, the share scored in
    ring layers."""
    del run
    ring = counters("attn_", "decode", attn="window") \
        .get("attn_rows_read_total", 0.0)
    full = counters("attn_", "decode", attn="full") \
        .get("attn_rows_read_total", 0.0)
    if not ring + full:
        return None
    log(f"attention counters, decode: {ring:.0f} rows scored in ring "
        f"layers, {full:.0f} in full layers")
    return 100.0 * ring / (ring + full)


def decode_hbm_share_pct(run: dict):
    """Bytes the traced decode rounds had to read over their device
    time at the chip's peak bandwidth: ``costs_kexaone
    .decode_round_bytes`` with the counters' mean of held experts
    touched a round (summed over the sparse layers) and the window's
    mean rows a round, in full layers and in rings, as
    ``readers.decode_hbm_share_pct`` takes them."""
    mod = readers._module(run, r"serve_step")
    rounds = len(run["round_seconds"])
    c = _routing("decode")
    if mod is None or not rounds or c is None:
        return None
    n, secs = mod
    cfg = run["cfg"]
    sparse = costs_kexaone.layer_counts(cfg)["sparse"]
    touched = c.get("moe_held_experts_touched_total", 0.0) \
        / c["moe_calls_total"] * sparse
    depths = [d for _, d in readers.decode_tokens_in_window(run)]
    window = cfg["sliding_window"]
    need = n * costs_kexaone.decode_round_bytes(
        cfg, touched, sum(depths) / rounds,
        sum(min(d, window) for d in depths) / rounds)
    return 100.0 * need / (secs * run["peaks"]["hbm_bytes_per_s"])


def prefill_flops_share_pct(run: dict):
    """Operations the traced prefills needed over their device time at
    the chip's peak: each traced execution is charged
    ``costs_kexaone.prefill_flops`` of its own span's ``tokens``
    (``readers.prefill_flops_share``), with the counters' mean pairs a token
    a sparse layer."""
    c = _routing("prefill")
    if c is None:
        return None
    cfg = run["cfg"]
    pairs = c.get("moe_held_pairs_total", 0.0) \
        / (c["moe_picks_total"] / cfg["num_experts_per_tok"])
    return readers.prefill_flops_share(
        run, lambda t, _: costs_kexaone.prefill_flops(cfg, t, pairs))
