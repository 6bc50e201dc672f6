"""``window_jit_seconds``

Trace + lower + compile seconds of the ``jax.monitoring`` events that
ended inside the window on the thread that runs the loop (the serve
loop's, or the one that calls ``Trainer.train``), from the program's
listener ring; events of other threads are logged beside it. 0 in a
sound run, and bounded to the window by the events' own stamps.
"""

from benchmark.lib import loop_records


def read(run: dict):
    return loop_records.window_jit_seconds(run)
