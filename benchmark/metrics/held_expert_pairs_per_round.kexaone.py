"""``held_expert_pairs_per_round.kexaone``

Token-expert pairs computed on this rank's held experts, a sparse layer
a decode round (``moe_held_pairs_total`` over ``moe_calls_total``, kind
decode). Expected: active rows x 8 picks x 16 held / 128.
"""

from benchmark.lib import readers_kexaone


def read(run: dict):
    return readers_kexaone.held_pairs_per_round(run)
