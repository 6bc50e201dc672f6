"""``itl_p95``

Gap between consecutive tokens of one request as its client receives
them, 95th percentile over all gaps of the requests due in the window.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.pct(readers.itls_ms(run), 95)
