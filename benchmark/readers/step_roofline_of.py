"""A step's share of the memory roofline, as `step_roofline` computes it,
with the least bytes counted by a function of a module a later PR added
beside `benchmark/rooflines.py` (`bytes_module`, say `rooflines_fanin`)."""

from __future__ import annotations

import importlib

from benchmark import peaks, rooflines
from benchmark.readers._common import step_seconds


def read(obs, modules, bytes_module, bytes_fn):
    seconds = step_seconds(obs, modules)
    if seconds is None:
        return None
    count = getattr(importlib.import_module(f"benchmark.{bytes_module}"),
                    bytes_fn)
    return rooflines.roofline_share(count(obs["config"], obs["chips"]),
                                    seconds,
                                    peaks.peaks_of(obs["device_kind"]))
