"""The readers of ``ax_k1`` (``program.readers``).

The counters are the program's own (``obs``' registry, summed on the
device over real tokens and published by the engine every 64 decode
rounds): the routing counts ``moe_*_total{kind,layer}``, among them
``moe_pick_groups_total`` (the expert groups a token's picks fell in),
and the attention rows ``attn_rows_*_total{kind,layer,attn="latent"}``.
They run from the process's first request: warm-up, the closed loop's
fill, the window and its drain. Every counter metric here is a ratio of
two of them, so the longer span moves it only as far as those phases
differ from the window.

The serve loop's spans are read from the traced run's file
(``host_spans``): ``serve/prefill_into`` carries ``tokens`` (prefilled),
``padded`` and ``cached`` (restored from the prefix store),
``serve/restore`` the ``blocks`` copied. The prefix store's own
accounting (``serve_kv_prefix_*_total``) is in the same registry as the
device counters and runs as long. A program without the counters or the
spans (the parent of the PR that brought the model cannot run the cell
at all) gives ``None`` everywhere.
"""

from __future__ import annotations

from benchmark.lib import costs_axk1, host_spans, readers
from benchmark.lib import trace_reduce as tr
from benchmark.lib.common import log
from benchmark.lib.readers_kexaone import (  # noqa: F401 - read by metrics
    _routing,
    counters,
    held_pairs_per_round,
)


def held_experts_touched_share_pct(run: dict):
    c = _routing("decode")
    if c is None:
        return None
    return 100.0 * c.get("moe_held_experts_touched_total", 0.0) \
        / c["moe_calls_total"] / run["cfg"]["n_routed_experts"]


def pick_groups_per_token(run: dict):
    """Distinct expert groups a token's picks fell in, mean over every
    real token of both program kinds: at most ``topk_group``."""
    groups = tokens = 0.0
    for kind in ("prefill", "decode"):
        c = _routing(kind)
        if c is not None:
            groups += c.get("moe_pick_groups_total", 0.0)
            tokens += c["moe_picks_total"] / run["cfg"]["num_experts_per_tok"]
    return groups / tokens if tokens and groups else None


def latent_rows_attended_share_pct(run: dict):
    """Latent rows inside the mask over latent rows the decode rounds
    scored (the row's whole padded length)."""
    del run
    c = counters("attn_", "decode", attn="latent")
    if not c.get("attn_rows_read_total"):
        return None
    return 100.0 * c.get("attn_rows_attended_total", 0.0) \
        / c["attn_rows_read_total"]


def decode_hbm_share_pct(run: dict):
    """Bytes the traced decode rounds had to read over their device
    time at the chip's peak bandwidth: ``costs_axk1.decode_round_bytes``
    with the counters' mean of held experts touched a round (summed over
    the sparse layers) and the window's mean rows a round, as
    ``readers.decode_hbm_share_pct`` takes them."""
    mod = readers._module(run, r"serve_step")
    rounds = len(run["round_seconds"])
    c = _routing("decode")
    if mod is None or not rounds or c is None:
        return None
    n, secs = mod
    cfg = run["cfg"]
    touched = c.get("moe_held_experts_touched_total", 0.0) \
        / c["moe_calls_total"] * costs_axk1.layer_counts(cfg)["sparse"]
    rows = sum(d for _, d in readers.decode_tokens_in_window(run)) / rounds
    need = n * costs_axk1.decode_round_bytes(cfg, touched, rows)
    return 100.0 * need / (secs * run["peaks"]["hbm_bytes_per_s"])


def prefix_cached_token_share_pct(run: dict):
    """Of the prompt tokens the callers' requests brought, the share the
    prefix store restored, by the engine's own accounting:
    ``serve_kv_prefix_tokens_saved_total`` (``PrefixCache._account``'s
    hits) over the prompt tokens of every request of the closed loop
    that got a first token. Counter and requests both run over the fill,
    the window and the drain (every admission, not the 6-9 of a 4 s
    trace), so the sixteen callers' first requests, which find the store
    empty, are in it. The warm-up's prompts are random, miss, add
    nothing to the counter and are left out of the requests."""
    from pytorch_distributed_nn_tpu import obs

    snap = obs.get_registry().snapshot()
    hits = snap.get("serve_kv_prefix_hits_total", 0.0)
    misses = snap.get("serve_kv_prefix_misses_total", 0.0)
    asked = sum(len(s.prompt) for s in run.get("sent") or () if s.arrivals)
    if not hits + misses or not asked:
        return None
    saved = snap.get("serve_kv_prefix_tokens_saved_total", 0.0)
    log(f"prefix store, from the process's first request: {hits:.0f} hits "
        f"of {saved / max(hits, 1.0):.1f} tokens in the mean, "
        f"{misses:.0f} misses, "
        f"{snap.get('serve_kv_prefix_evictions_total', 0.0):.0f} blocks "
        f"evicted; the callers' admitted prompts hold {asked} tokens")
    return 100.0 * saved / asked


def restore_share_pct(run: dict):
    """``serve/restore`` span time (the host's dispatch of the block
    copies) over the traced window."""
    a = host_spans.of_run(run)
    if a is None:
        return None
    spans = [(s, e) for n, s, e, _ in a["spans"] if n == "serve/restore"]
    if not spans:
        return None
    return 100.0 * tr.total(spans) / a["window_ns"]


def prefill_flops_share_pct(run: dict):
    """Operations the traced prefills needed over their device time at
    the chip's peak: each traced execution is charged
    ``costs_axk1.prefill_flops`` of its own span's ``tokens`` and
    ``cached`` (``readers.prefill_flops_share``), with the counters' mean
    pairs a token a sparse layer."""
    c = _routing("prefill")
    if c is None:
        return None
    cfg = run["cfg"]
    pairs = c.get("moe_held_pairs_total", 0.0) \
        / (c["moe_picks_total"] / cfg["num_experts_per_tok"])
    return readers.prefill_flops_share(
        run, lambda t, cached: costs_axk1.prefill_flops(cfg, t, cached,
                                                        pairs))
