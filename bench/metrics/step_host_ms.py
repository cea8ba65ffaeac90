"""Median over the traced engine steps of the host's own time in
``ServeEngine.step``: each ``serve.step`` span less its ``serve.sync.*``
spans, where the host waits on the device."""
import statistics

from bench import spans as sp


def read(run):
    per_step = sp.step_host_ms(sp.of_run(run))
    return statistics.median(per_step) if per_step else None
