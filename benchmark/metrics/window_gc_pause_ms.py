"""``window_gc_pause_ms``

Seconds the garbage collector held the process inside the window, from
the serve loop's round records (``gc_s``), in milliseconds (the closed-loop served cells).
"""

from benchmark.lib import loop_records


def read(run: dict):
    return loop_records.gc_pause_ms(run)
