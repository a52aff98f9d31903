"""Device time of the step program per step run: the trace's executions of
the named modules over the steps the system ran in the traced stretch."""

from benchmark.readers._common import step_seconds


def read(obs, modules):
    seconds = step_seconds(obs, modules)
    return None if seconds is None else 1e3 * seconds
