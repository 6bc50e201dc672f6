"""``prefill_share``

Engine wall in prefill over the window: admitted to first token
(``readers.prefill_share_pct``), or what the model's own readers say
ends a prefill (a block decoder's first token is its first block's
last step, rounds later: ``readers_sdar.prefill_share_pct`` ends at
``t_prefilled``).
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.of_model(run, "prefill_share_pct",
                            fallback=readers.prefill_share_pct)
