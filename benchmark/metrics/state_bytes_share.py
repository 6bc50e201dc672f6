"""``state_bytes_share``

Of the bytes a decode round must move, the share that is recurrent
state (an SSM's state and carried inputs, a short convolution's
tails): how much of the round the mechanism is.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.of_model(run, "state_bytes_share_pct")
