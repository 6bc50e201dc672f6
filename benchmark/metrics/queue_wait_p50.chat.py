"""``queue_wait_p50.chat``

Scheduler lifecycle: due to admitted, median over the window's
requests.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.pct(readers.queue_waits_ms(run), 50)
