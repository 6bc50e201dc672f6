"""``prefill_rows_attended_share.axk1``

Latent rows inside the real queries' masks over latent rows the prefill
programs read for them (``attn_rows_attended_total`` over
``attn_rows_read_total``, kind prefill, attn latent): how much of what
the expanded path reads a mask lets through. A program that scores
every query against the row's whole padded length reads ~45 % here
(whole prompts ~40 %, suffixes behind restored rows ~78 %); one whose
query tiles visit only the key tiles at or under their largest position
reads 85-95 %, by its tiles (``PERF.md`` sec. 3).
"""

from benchmark.lib import readers_axk1


def read(run: dict):
    del run
    c = readers_axk1.counters("attn_", "prefill", attn="latent")
    if not c.get("attn_rows_read_total"):
        return None
    return 100.0 * c.get("attn_rows_attended_total", 0.0) \
        / c["attn_rows_read_total"]
