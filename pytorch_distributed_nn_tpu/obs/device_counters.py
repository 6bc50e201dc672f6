"""Counters a model sums on the device, published to the registry.

A served model may count what only its own program sees (a mixture of
experts: which experts its tokens picked; a state-space or a short
convolution layer: the positions that advanced its state) without a
fetch of its own every round. It keeps one 1-D ``uint32`` leaf of
running totals in its ``cache`` collection, which every program execution adds to on the
device, and declares it: ``device_counter_leaf`` is the leaf's path in
that collection, ``device_counter_names()`` names the entries,
``((metric, labels), ...)``. The serving engine sums that leaf, and no
other, when a prefilled row joins the batch, reads it at a slow fixed
rate and hands it to
:meth:`DeviceCounters.publish`, which adds what came since the last
reading to the registry's counters.
"""

from __future__ import annotations

import numpy as np

from pytorch_distributed_nn_tpu.obs.registry import get_registry

class DeviceCounters:
    def __init__(self, names: tuple) -> None:
        reg = get_registry()
        # literal names: scripts/obs_metrics.py lists instruments by them
        labels = ("kind", "layer")
        made = {
            "moe_calls_total": reg.counter(
                "moe_calls_total", "executions of an expert layer",
                labels=labels),
            "moe_picks_total": reg.counter(
                "moe_picks_total", "router picks of real tokens",
                labels=labels),
            "moe_zero_expert_picks_total": reg.counter(
                "moe_zero_expert_picks_total",
                "router picks that went to zero-compute experts",
                labels=labels),
            "moe_held_pairs_total": reg.counter(
                "moe_held_pairs_total",
                "token-expert pairs computed on the experts this rank "
                "holds", labels=labels),
            "moe_held_experts_touched_total": reg.counter(
                "moe_held_experts_touched_total",
                "held experts some token picked, summed over executions",
                labels=labels),
            "moe_pick_groups_total": reg.counter(
                "moe_pick_groups_total",
                "distinct expert groups a real token's picks fell in, "
                "summed over tokens (group-limited routing)",
                labels=labels),
            "ssm_calls_total": reg.counter(
                "ssm_calls_total", "executions of a state-space layer",
                labels=labels),
            "ssm_tokens_total": reg.counter(
                "ssm_tokens_total",
                "real positions that advanced a state-space layer's "
                "state", labels=labels),
            "conv_calls_total": reg.counter(
                "conv_calls_total",
                "executions of a short-convolution layer", labels=labels),
            "conv_tokens_total": reg.counter(
                "conv_tokens_total",
                "real positions that moved a short-convolution layer's "
                "carried inputs", labels=labels),
            "retention_calls_total": reg.counter(
                "retention_calls_total",
                "executions of a power-retention layer", labels=labels),
            "retention_tokens_total": reg.counter(
                "retention_tokens_total",
                "real positions that advanced a power-retention layer's "
                "state", labels=labels),
            "block_forwards_total": reg.counter(
                "block_forwards_total",
                "forwards of a block of positions by a block decoder's "
                "round (a live row a round)"),
            "block_commits_total": reg.counter(
                "block_commits_total",
                "block forwards that found no position masked and only "
                "committed the block (none: see "
                "block_commits_fused_total)"),
            "block_positions_unmasked_total": reg.counter(
                "block_positions_unmasked_total",
                "masked positions that took a token in a denoising step"),
            "block_tokens_emitted_total": reg.counter(
                "block_tokens_emitted_total",
                "tokens a block's last step handed to its request"),
            "block_commits_fused_total": reg.counter(
                "block_commits_fused_total",
                "block forwards that wrote a finished block's keys and "
                "values beside a denoising step of the next block"),
            "attn_rows_attended_total": reg.counter(
                "attn_rows_attended_total",
                "cached key rows inside the masks of real queries",
                labels=labels + ("attn",)),
            "attn_rows_read_total": reg.counter(
                "attn_rows_read_total",
                "cached key rows the program scored for real queries "
                "(a window layer's ring, or a row's whole length)",
                labels=labels + ("attn",)),
        }
        self._targets = [(made[name], labels) for name, labels in names]
        self._last = np.zeros((len(names),), np.uint32)

    def publish(self, totals) -> None:
        """``totals``: the leaf as fetched. The difference is taken in
        uint32, so a total that wrapped still gives what was added."""
        totals = np.asarray(totals, np.uint32)
        for (counter, labels), n in zip(self._targets, totals - self._last):
            if n:
                counter.inc(int(n), **labels)
        self._last = totals
