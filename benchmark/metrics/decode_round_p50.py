"""``decode_round_p50``

Median of ``engine.round_seconds`` inside the window (the closed-loop
served cells).
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.decode_round_p50_ms(run)
