"""``held_expert_pairs_per_round.sdar``

Token-expert pairs computed on the held experts, a layer a round
(``moe_held_pairs_total`` over ``moe_calls_total``, kind decode).
Expected: live rows x 4 positions x 8 picks, every expert held.
"""

from benchmark.lib import readers_sdar


def read(run: dict):
    return readers_sdar.held_pairs_per_round(run)
