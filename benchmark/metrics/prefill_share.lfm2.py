"""``prefill_share.lfm2``

Engine wall in prefill (admitted to first token) over the window.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.prefill_share_pct(run)
