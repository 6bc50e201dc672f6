"""``admit_stall_p50.chat``

Median length of a ``serve/admit`` span in the trace: how long every
slot stands still for one admission pass.
"""

from benchmark.lib import host_spans


def read(run: dict):
    return host_spans.span_p50_ms(run, "serve/admit")
