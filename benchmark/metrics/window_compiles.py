"""``window_compiles``

Programs built or loaded inside the window (``jax.monitoring``
backend-compile events between its first and last instant): 0 when
warm-up covered every shape.
"""


def read(run: dict):
    return float(run["compile"]["compiles"])
