"""``decode_round_p50.kexaone``

Median of ``engine.round_seconds`` inside the window.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.decode_round_p50_ms(run)
