"""``device_cache_write_share``

Chip 0's busy time in the traced window spent
writing new cache rows and state, the engine's row, slot-state and
block-store copies among it (scope part ``cache_write``),
in % of that busy time. The traced run's device events joined with the
program's own map from compiled instruction to scope
(``benchmark/lib/scope_shares.py``; the closed-loop served cells).
"""

from benchmark.lib import scope_shares


def read(run: dict):
    return scope_shares.share_pct(run, "cache_write")
