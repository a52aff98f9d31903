"""Helpers the readers share."""

from __future__ import annotations

import re


def module_seconds(obs: dict, patterns) -> float:
    """Device seconds of the program executions whose module name matches,
    inside the traced stretch, on the fullest device."""
    from benchmark import xplane

    sums = xplane.module_sums(obs["trace"], obs["trace_a"], obs["trace_b"],
                              obs["trace_fullest"])
    return sum(v for k, v in sums.items()
               if any(re.search(p, k) for p in patterns))


def step_seconds(obs: dict, modules):
    """Device seconds per step of the named step programs over the traced
    stretch (the driver counts the steps it ran there); None where no step
    ran or no such program executed."""
    steps = int(obs.get("steps_in_trace") or 0)
    seconds = module_seconds(obs, modules)
    if steps <= 0 or seconds <= 0:
        return None
    return seconds / steps
