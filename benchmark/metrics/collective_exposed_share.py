"""``collective_exposed_share``

Time a collective runs or is in flight on chip 0 with no compute
operation running, over the traced window.
"""


def read(run: dict):
    tr = run.get("trace")
    if tr is None:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
