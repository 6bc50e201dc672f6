"""``tokens_per_forward.sdar``

Tokens handed to requests over forwards of a block (a live row a
round), by the program's counters (``block_tokens_emitted_total`` over
``block_forwards_total``): a block of 4 unmasked in 2 steps and
committed by a third forward gives 4 / 3.
"""

from benchmark.lib import readers_sdar


def read(run: dict):
    return readers_sdar.tokens_per_forward(run)
