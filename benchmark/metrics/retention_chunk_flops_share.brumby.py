"""``retention_chunk_flops_share.brumby``

The ``retention_chunk`` kernel against the chip's peak in a prefill: the
operations the retention of the traced prefills needed, at their spans'
real tokens (``costs_brumby.retention_flops``: the least either form
asks for), over the device time of the kernel's executions inside them.
The op is this model's alone.
"""

from benchmark.lib import readers_brumby


def read(run: dict):
    return readers_brumby.retention_chunk_flops_share_pct(run)
