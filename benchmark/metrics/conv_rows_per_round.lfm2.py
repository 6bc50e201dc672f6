"""``conv_rows_per_round.lfm2``

Rows whose carried inputs a decode round's short convolutions moved,
in the mean over the layers and the rounds the counters saw
(``conv_tokens_total`` over ``conv_calls_total``, kind decode): the
sequences a round advanced, of the cell's 64 slots. The ``conv_*``
counters are this model's alone, and every per-row term of its
``decode_hbm_share`` and ``state_bytes_share`` is this many rows.
"""

from benchmark.lib import readers_lfm2


def read(run: dict):
    return readers_lfm2.conv_rows_per_round(run)
