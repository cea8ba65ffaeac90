"""Share of the engine's device time spent in its chunk-prefill program."""
from bench import trace as tr


def read(run):
    t = tr.op_time_by_kind(run["trace"])
    total = sum(t.values())
    if total <= 0 or t["prefill"] + t["decode"] <= 0:
        return None
    return 100.0 * t["prefill"] / total
