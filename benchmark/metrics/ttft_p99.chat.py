"""``ttft_p99.chat``

For reading beside ``ttft_p90``, never for deciding.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.pct(readers.ttfts_ms(run), 99)
