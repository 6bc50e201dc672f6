"""``collectives_per_step``

Collective operations chip 0 issued in the trace, per execution of the
step program.
"""

from benchmark.lib import readers


def read(run: dict):
    mod = readers.step_module(run)
    if mod is None or not run["trace"]["collectives_launched"]:
        return None
    return run["trace"]["collectives_launched"] / mod[0]
