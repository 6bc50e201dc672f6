"""``round_host_p50.chat``

Median length of a ``serve/round_host`` span in the trace: host time a
round from the token fetch to the end of ``step()`` (gauges, flight,
watchtower, autoscale, xray, meter, collect, slot sync).
"""

from benchmark.lib import host_spans


def read(run: dict):
    return host_spans.span_p50_ms(run, "serve/round_host")
