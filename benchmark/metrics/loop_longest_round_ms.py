"""``loop_longest_round_ms``

The longest round of the window by the serve loop's own records (a
round's wall runs from the end of the round before it): within a few
ms of the longest admission in a sound run; a stall is a number here,
and the reader logs what filled the three longest (the closed-loop served cells).
"""

from benchmark.lib import loop_records


def read(run: dict):
    return loop_records.longest_round_ms(run)
