"""``device_ffn_share.chat``

Chip 0's busy time in the traced window spent
in the dense MLPs and the experts with their router, sort, gathers and
sum (scope part ``ffn``),
in % of that busy time. The traced run's device events joined with the
program's own map from compiled instruction to scope
(``benchmark/lib/scope_shares.py``; the chat cell).
"""

from benchmark.lib import scope_shares


def read(run: dict):
    return scope_shares.share_pct(run, "ffn")
