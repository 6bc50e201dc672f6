"""``full_rows_attended_share.kexaone``

In the full-attention layers, cache rows inside the mask over cache
rows the decode rounds scored (``attn_rows_attended_total`` over
``attn_rows_read_total``, kind decode, attn full): how much of a round's
read of the whole padded row a sequence needs. The rings score 128 of
128 once a row is past 128.
"""

from benchmark.lib import readers_kexaone


def read(run: dict):
    return readers_kexaone.full_rows_attended_share_pct(run)
