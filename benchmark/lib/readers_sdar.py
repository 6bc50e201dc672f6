"""The readers of ``sdar_30b_a3b`` (``program.readers``).

The counters are the program's own (``obs``' registry, summed on the
device and published by the engine every 64 rounds): a block decoder's
``block_forwards_total`` (a live row a round),
``block_positions_unmasked_total`` and ``block_tokens_emitted_total``,
and by layer and program kind the routing counts ``moe_*_total`` and
the attention rows ``attn_rows_*_total`` (``kind`` ``decode`` is the
block round). They run from the process's first request: warm-up, the
closed loop's fill, the window and its drain. Every metric here is a
ratio of two of them. A program without the counters (the parent of
the PR that brought them cannot build the model at all) gives ``None``
everywhere.
"""

from __future__ import annotations

from benchmark.lib import costs_sdar, host_spans, readers
from benchmark.lib.common import log
from benchmark.lib.readers_kexaone import counters


def _blocks():
    from pytorch_distributed_nn_tpu import obs

    snap = obs.get_registry().snapshot()
    c = {k: v for k, v in snap.items() if k.startswith("block_")}
    return c if c.get("block_forwards_total") else None


def _routing(kind: str):
    c = counters("moe_", kind)
    return c if c.get("moe_calls_total") else None


def _block_length(run: dict) -> int:
    return int(run["cfg"]["generation"]["block_length"])


def tokens_per_forward(run: dict):
    """Tokens handed to requests over forwards of a block (a live row a
    round): a block of B unmasked in S steps gives B / S, the step that
    leaves it whole handing it out (its keys and values are written by
    the row's next forward, beside that block's first step)."""
    del run
    c = _blocks()
    if c is None:
        return None
    log(f"block counters: {c['block_forwards_total']:.0f} forwards, "
        f"{c.get('block_commits_fused_total', 0.0):.0f} of them writing "
        f"an owed block, "
        f"{c.get('block_positions_unmasked_total', 0.0):.0f} positions "
        f"unmasked, {c.get('block_tokens_emitted_total', 0.0):.0f} tokens "
        f"emitted")
    return c.get("block_tokens_emitted_total", 0.0) \
        / c["block_forwards_total"]


def cache_rows_attended_share_pct(run: dict):
    """Cache rows inside the masks of a round's queries over cache rows
    the round scored for them (the row's whole padded length)."""
    del run
    c = counters("attn_", "decode")
    if not c.get("attn_rows_read_total"):
        return None
    return 100.0 * c.get("attn_rows_attended_total", 0.0) \
        / c["attn_rows_read_total"]


def held_pairs_per_round(run: dict):
    c = _routing("decode")
    if c is None:
        return None
    k = run["cfg"]["num_experts_per_tok"]
    positions = c["moe_picks_total"] / c["moe_calls_total"] / k
    log(f"routing counters, rounds: {c['moe_calls_total']:.0f} layer "
        f"executions, {positions:.2f} positions a round "
        f"({positions / _block_length(run):.2f} live rows of "
        f"{run['slots']})")
    return c.get("moe_held_pairs_total", 0.0) / c["moe_calls_total"]


def held_experts_touched_share_pct(run: dict):
    c = _routing("decode")
    if c is None:
        return None
    return 100.0 * c.get("moe_held_experts_touched_total", 0.0) \
        / c["moe_calls_total"] / run["cfg"]["num_experts"]


def prefill_share_pct(run: dict):
    """Engine wall between admitting a request and the end of its
    prefill (``t_prefilled``; a block decoder's first token is its first
    block's commit, rounds later, and is not the prefill's), summed over
    the requests admitted in the window, over the window."""
    t0, t1 = run["t0"], run["t1"]
    wall = 0.0
    for s in run["sent"]:
        req = s.request
        done = getattr(req, "t_prefilled", 0.0) if req is not None else 0.0
        if done > 0.0 and t0 <= req.t_admit <= t1:
            wall += done - req.t_admit
    return 100.0 * wall / readers.window_s(run)


def decode_hbm_share_pct(run: dict):
    """Bytes the traced rounds had to move over their device time at
    the chip's peak bandwidth: ``costs_sdar.round_bytes`` with the
    counters' means a round of held experts touched (summed over the
    layers), cache rows attended (a live row's rows once a layer: the
    counter holds them once a query, B a row) and positions fed."""
    mod = readers._module(run, r"serve_step")
    c = _routing("decode")
    a = counters("attn_", "decode")
    if mod is None or c is None or not a.get("attn_rows_attended_total"):
        return None
    n, secs = mod
    cfg = run["cfg"]
    layers = cfg["num_hidden_layers"]
    calls = c["moe_calls_total"]
    touched = c.get("moe_held_experts_touched_total", 0.0) / calls * layers
    positions = c["moe_picks_total"] / calls / cfg["num_experts_per_tok"]
    rows = a["attn_rows_attended_total"] / calls / _block_length(run)
    need = n * costs_sdar.round_bytes(cfg, touched, rows, positions)
    return 100.0 * need / (secs * run["peaks"]["hbm_bytes_per_s"])


def grouped_experts_hbm_share_pct(run: dict):
    """The ``grouped_experts`` kernel's own roofline in the block round:
    the bytes of the experts the traced rounds touched (the counters'
    mean a round, summed over the layers, at
    ``costs_sdar.experts_bytes``) over the device time of the
    ``grouped_experts`` operations inside ``serve_step`` executions on
    chip 0, at the chip's peak bandwidth. At a round's fifteen or so rows
    an expert the kernel is bound by the experts' bytes."""
    c = _routing("decode")
    inside = readers.op_inside_module(run, "grouped_experts", "serve_step")
    if c is None or inside is None:
        return None
    n, secs = inside
    cfg = run["cfg"]
    touched = c.get("moe_held_experts_touched_total", 0.0) \
        / c["moe_calls_total"] * cfg["num_hidden_layers"]
    return 100.0 * n * costs_sdar.experts_bytes(cfg, touched) \
        / (secs * run["peaks"]["hbm_bytes_per_s"])


def prefill_flops_share_pct(run: dict):
    """Operations the traced prefills needed over their device time at
    the chip's peak. A block decoder's prefill fetches nothing, so its
    ``serve/prefill_into`` span ends at the dispatch and the execution
    follows it: each span that prefilled something is paired, in order,
    with the first ``serve_prefill`` execution on chip 0 that starts
    after the span does, and charged ``costs_sdar.prefill_flops`` of the
    span's ``tokens`` and ``cached`` with the counters' mean pairs a
    token a layer; an execution no span precedes is left out, time and
    all."""
    a = host_spans.of_run(run)
    c = _routing("prefill")
    if a is None or c is None:
        return None
    into = sorted((s, int(st["tokens"]), int(st["cached"]))
                  for n, s, e, st in a["spans"]
                  if n == "serve/prefill_into" and int(st["tokens"]) > 0)
    dev = readers.chip0_events(run)
    if dev is None:
        return None
    execs = sorted((s, e) for n, s, e in dev["modules"]
                   if "serve_prefill" in n)
    cfg = run["cfg"]
    pairs = c.get("moe_held_pairs_total", 0.0) \
        / (c["moe_picks_total"] / cfg["num_experts_per_tok"])
    need = secs = 0.0
    k = paired = 0
    for start, tokens, cached in into:
        while k < len(execs) and execs[k][0] < start:
            k += 1
        if k == len(execs):
            break
        s, e = execs[k]
        k += 1
        paired += 1
        need += costs_sdar.prefill_flops(cfg, tokens, cached,
                                         _block_length(run), pairs)
        secs += (e - s) / 1e9
    if not secs:
        return None
    log(f"traced prefills paired with their spans: {paired} of "
        f"{len(execs)} executions and {len(into)} spans, {secs:.3f} s; "
        f"{pairs:.3f} pairs a token a layer on held experts")
    return 100.0 * need / (secs * run["peaks"]["bf16_flops"])
