"""``step_device_ms.train``

Device time of one execution of the step program on chip 0, mean over
the trace.
"""

from benchmark.lib import readers


def read(run: dict):
    mod = readers.step_module(run)
    return None if mod is None else 1e3 * mod[1] / mod[0]
