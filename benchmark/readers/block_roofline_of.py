"""One block's share of its own memory roofline, as `scope_roofline_of`
computes a layer's: the least bytes the block must move in a step (a
function of a module beside `benchmark/rooflines.py`, from the
configuration's shapes), over the chip's published bandwidth, over the
block's device seconds a step: self time of the step program's operations
whose deepest `akka.` scope is `block` under the layer `layer` in the traced
stretch (one row of the table `scope_share` builds), over the steps run
there. A program with no such block, or a stretch with no step: nothing to
read."""

from __future__ import annotations

import importlib

from benchmark import peaks, rooflines
from benchmark.readers import scope_share


def read(obs, modules, layer, block, bytes_module, bytes_fn):
    steps = int(obs.get("steps_in_trace") or 0)
    if steps <= 0:
        return None
    seconds = sum(row[2] for row in scope_share._table(obs, modules)["rows"]
                  if row[0] == layer and row[1] == block)
    if seconds <= 0:
        return None
    count = getattr(importlib.import_module(f"benchmark.{bytes_module}"),
                    bytes_fn)
    return rooflines.roofline_share(count(obs["config"], obs["chips"]),
                                    seconds / steps,
                                    peaks.peaks_of(obs["device_kind"]))
