"""``decode_hbm_share``

Bytes the traced decode rounds had to move over their ``serve_step``
device time at the chip's peak bandwidth. What a round must move is
the model's: its readers (``program.readers`` of the configuration)
count the weights read once, the held experts some row picked by the
program's counters, the cached rows attended or the state carried, by
its ``costs_<model>.py``.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.of_model(run, "decode_hbm_share_pct")
