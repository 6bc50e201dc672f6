"""``prefill_pad_share.lfm2``

1 - sum(``tokens``) / sum(``padded``) over the ``serve/prefill_into``
spans in the trace: prompt positions computed for padding.
"""

from benchmark.lib import host_spans


def read(run: dict):
    return host_spans.prefill_pad_share_pct(run)
