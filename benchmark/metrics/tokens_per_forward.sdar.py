"""``tokens_per_forward.sdar``

Tokens handed to requests over forwards of a block (a live row a
round), by the program's counters (``block_tokens_emitted_total`` over
``block_forwards_total``): a block of 4 unmasked in 2 steps gives
4 / 2; the step that leaves it whole hands it out, and its keys and
values are written by the row's next forward, beside the next block's
first step (no forward only commits, since PR 44).
"""

from benchmark.lib import readers_sdar


def read(run: dict):
    return readers_sdar.tokens_per_forward(run)
