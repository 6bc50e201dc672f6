"""``setup_s``

From the first line of ``run.py`` to the window's first step or request:
imports, weights, compile-cache loads, warm-up, the schedule
and its token ids (and a closed loop's fill), less the seconds JAX's
first ``jax.devices()`` waited for the TPU runtime to hand over the chip.
"""


def read(run: dict):
    return run["setup_s"]
