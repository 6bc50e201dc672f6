"""``prefill_flops_share``

Operations the traced prefills needed over their ``serve_prefill``
device time at the chip's peak. Each traced execution is charged the
work of its own ``serve/prefill_into`` span's ``tokens``
(``readers.paired_prefills``; a block decoder's span ends at the
dispatch and is paired in order), by the model's ``costs_<model>.py``:
the mix of prompts in the traced seconds is the traced one, not the
window's mean.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.of_model(run, "prefill_flops_share_pct")
