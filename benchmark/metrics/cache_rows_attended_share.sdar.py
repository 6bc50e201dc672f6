"""``cache_rows_attended_share.sdar``

Cache rows inside the masks of a round's queries over cache rows the
round scored for them, the row's whole padded length
(``attn_rows_attended_total`` over ``attn_rows_read_total``, kind
decode): how much of a round's read of the padded row a sequence
needs.
"""

from benchmark.lib import readers_sdar


def read(run: dict):
    return readers_sdar.cache_rows_attended_share_pct(run)
