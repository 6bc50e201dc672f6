"""``ttft_p90``

First token minus the time the request was due, 90th percentile
(nearest rank) over every request due in the window.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.pct(readers.ttfts_ms(run), 90)
