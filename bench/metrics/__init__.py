"""Per-layer metric readers, one module per metric, found by name.

Each ``read(run)`` takes the traced run (``run["trace"]``: the reduced
profiler trace, ``run["steps"]``: the host's record of every engine step
inside the traced window, ``run["arch"]``, ``run["engine"]``,
``run["peaks"]``) and returns the metric, or ``None`` where the run holds
nothing to read.
"""
