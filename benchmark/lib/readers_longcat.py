"""The readers of ``longcat_flash_omni`` (``program.readers``).

The routing counters are the program's own (``obs``' registry:
``moe_*_total{kind,layer}``, summed on the device over real tokens and
published by the engine every 64 decode rounds). They run from the
process's first request: warm-up, the closed loop's fill, the window
and its drain. Every metric here is a ratio of two of them, so the
longer span moves it only as far as those phases differ from the
window. A program without the counters (the parent of the PR that
brought them) gives ``None`` everywhere.
"""

from __future__ import annotations

from benchmark.lib import costs_longcat, readers
from benchmark.lib.common import log


def counters(kind: str):
    """``{name: [per-layer totals]}`` of one program kind (``prefill``,
    ``decode``), or None where the program counts nothing."""
    from pytorch_distributed_nn_tpu import obs

    out: dict = {}
    for key, value in obs.get_registry().snapshot().items():
        name, _, labels = key.partition("{")
        if name.startswith("moe_") and f'kind="{kind}"' in labels:
            out.setdefault(name, []).append(value)
    if not out.get("moe_calls_total") or not sum(out["moe_calls_total"]):
        return None
    return out


def _layer_mean_per_call(c: dict, name: str) -> float:
    """Mean of a counter over a layer's executions, mean over layers."""
    return sum(c.get(name, [0.0])) / sum(c["moe_calls_total"])


def held_pairs_per_round(run: dict):
    c = counters("decode")
    if c is None:
        return None
    cfg = run["cfg"]
    pairs = _layer_mean_per_call(c, "moe_held_pairs_total")
    rows = _layer_mean_per_call(c, "moe_picks_total") / cfg["moe_topk"]
    routed = cfg["n_routed_experts"] * cfg["expert_parallel"]["ep_size"]
    share = cfg["n_routed_experts"] / (routed + cfg["zero_expert_num"])
    log(f"routing counters, decode: {sum(c['moe_calls_total']):.0f} layer "
        f"executions, {rows:.2f} active rows a round of {run['slots']}, "
        f"{pairs:.3f} pairs a layer a round on held experts against "
        f"{rows * cfg['moe_topk'] * share:.3f} at an even spread; by layer "
        f"{[round(p / n, 2) for p, n in zip(c['moe_held_pairs_total'], c['moe_calls_total'])]}")
    return pairs


def held_experts_touched_share_pct(run: dict):
    c = counters("decode")
    if c is None:
        return None
    return 100.0 * _layer_mean_per_call(
        c, "moe_held_experts_touched_total") \
        / run["cfg"]["n_routed_experts"]


def zero_expert_pick_share_pct(run: dict):
    del run
    zero = picks = 0.0
    for kind in ("prefill", "decode"):
        c = counters(kind)
        if c is not None:
            zero += sum(c.get("moe_zero_expert_picks_total", [0.0]))
            picks += sum(c["moe_picks_total"])
    return 100.0 * zero / picks if picks else None


def decode_hbm_share_pct(run: dict):
    """Bytes the traced decode rounds had to read over their device
    time at the chip's peak bandwidth: ``costs_longcat
    .decode_round_bytes`` with the counters' mean of held experts
    touched a round (summed over the layers) and the window's mean rows
    a round, as ``readers.decode_hbm_share_pct`` takes them."""
    mod = readers._module(run, r"serve_step")
    rounds = len(run["round_seconds"])
    c = counters("decode")
    if mod is None or not rounds or c is None:
        return None
    n, secs = mod
    cfg = run["cfg"]
    touched = _layer_mean_per_call(c, "moe_held_experts_touched_total") \
        * cfg["num_layers"]
    rows = sum(d for _, d in readers.decode_tokens_in_window(run)) / rounds
    need = n * costs_longcat.decode_round_bytes(cfg, touched, rows)
    return 100.0 * need / (secs * run["peaks"]["hbm_bytes_per_s"])


def prefill_flops_share_pct(run: dict):
    """Operations the traced prefills needed over their device time at
    the chip's peak: each traced execution is charged
    ``costs_longcat.prefill_flops`` of its own span's ``tokens``
    (``readers.prefill_flops_share``), with the counters' mean pairs a token
    a layer."""
    c = counters("prefill")
    if c is None:
        return None
    cfg = run["cfg"]
    tokens = sum(c["moe_picks_total"]) / cfg["moe_topk"]
    pairs = sum(c.get("moe_held_pairs_total", [0.0])) / tokens
    return readers.prefill_flops_share(
        run, lambda t, _: costs_longcat.prefill_flops(cfg, t, pairs))
