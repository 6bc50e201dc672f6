"""``prefill_flops_share.docs``

See ``readers.prefill_flops_share_pct``.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.prefill_flops_share_pct(run)
