"""``held_expert_pairs_per_round``

Token-expert pairs computed on the held experts, a layer a round
(``moe_held_pairs_total`` over ``moe_calls_total``, kind decode): the
rows the experts' products really have, under an expert-parallel
share's uneven spread or, where every expert is held, active rows (x a
block's positions) x the picks a token.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.of_model(run, "held_pairs_per_round")
