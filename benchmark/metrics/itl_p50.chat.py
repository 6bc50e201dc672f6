"""``itl_p50.chat``

For reading beside ``itl_p95``, never for deciding.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.pct(readers.itls_ms(run), 50)
