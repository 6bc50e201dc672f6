"""``device_unscoped_share.chat``

Chip 0's busy time in the traced window spent
in instructions the program's map gives no part: no ``op_name``, unknown
to the map, or ambiguous between two bucket programs (``unscoped``: the
measure of the map's own coverage),
in % of that busy time. The traced run's device events joined with the
program's own map from compiled instruction to scope
(``benchmark/lib/scope_shares.py``; the chat cell).
"""

from benchmark.lib import scope_shares


def read(run: dict):
    return scope_shares.share_pct(run, "unscoped")
