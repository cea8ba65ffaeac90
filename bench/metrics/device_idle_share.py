"""Share of the traced window in which no operation ran on the chip."""
from bench import trace as tr


def read(run):
    w = tr.window_s(run["trace"])
    if w <= 0 or not run["trace"]["ops"]:
        return None
    return 100.0 * (1.0 - tr.busy_s(run["trace"]) / w)
