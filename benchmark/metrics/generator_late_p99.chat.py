"""``generator_late_p99.chat``

Replayer: sent minus due, 99th percentile. A starved generator must
not read as a fast server.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.pct(readers.generator_late_ms(run), 99)
