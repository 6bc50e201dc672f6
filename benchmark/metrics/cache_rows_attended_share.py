"""``cache_rows_attended_share``

Cache rows inside the masks of a round's real queries over cache rows
the round read for them (``attn_rows_attended_total`` over
``attn_rows_read_total``, kind decode): how much of what a round reads
of a padded row a sequence needs.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.of_model(run, "cache_rows_attended_share_pct")
