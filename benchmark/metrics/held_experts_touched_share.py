"""``held_experts_touched_share``

Of the routed experts this chip holds, the share some row of a decode
round picked, a layer a round in the mean
(``moe_held_experts_touched_total`` over ``moe_calls_total``, kind
decode, over the held experts): what of the experts' bytes a round
reads.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.of_model(run, "held_experts_touched_share_pct")
