"""``grouped_experts_hbm_share``

The ``grouped_experts`` kernel's own roofline in the decode round: the
bytes of the experts the traced rounds touched, by the program's
counters, over the device time of the kernel's executions inside
``serve_step``, at the chip's peak bandwidth (at a few rows an expert
the experts' bytes bound it).
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.of_model(run, "grouped_experts_hbm_share_pct")
