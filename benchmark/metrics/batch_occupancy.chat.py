"""``batch_occupancy.chat``

Tokens decode rounds produced in the window over rounds times slots.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.batch_occupancy_pct(run)
