"""``serve_throughput``

Prompt tokens (counted when the request's first token arrives) plus
output tokens (counted as each arrives) inside the window, over its
seconds.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.tokens_in_window(run) / readers.window_s(run)
