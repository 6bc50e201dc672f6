"""``device_forward_share.train``

Chip 0's busy time in the traced window spent
in the forward pass of the step (``jvp(`` and no ``transpose(`` in the
instruction's ``op_name``; scope part ``forward``),
in % of that busy time. The traced run's device events joined with the
program's own map from compiled instruction to scope
(``benchmark/lib/scope_shares.py``; the training cells).
"""

from benchmark.lib import scope_shares


def read(run: dict):
    return scope_shares.share_pct(run, "forward")
