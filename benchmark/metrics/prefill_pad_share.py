"""``prefill_pad_share``

1 - sum(``tokens``) / sum(``padded``) over the ``serve/prefill_into``
spans in the trace: prompt positions computed for padding (a prompt
pads to its bucket; behind restored rows the suffix pads to its own).
"""

from benchmark.lib import host_spans


def read(run: dict):
    return host_spans.prefill_pad_share_pct(run)
