"""One layer's share of its own memory roofline: the least bytes the layer
must move in a step (a function of a module beside `benchmark/rooflines.py`,
from the configuration's shapes), over the chip's published bandwidth, over
the layer's device seconds a step: self time of the step program's
operations under the `jax.named_scope` layer `scope` in the traced stretch
(the table `scope_share` builds), over the steps run there. A program with
no such scope, or a stretch with no step: nothing to read."""

from __future__ import annotations

import importlib

from benchmark import peaks, rooflines
from benchmark.readers import scope_share


def read(obs, modules, scope, bytes_module, bytes_fn):
    steps = int(obs.get("steps_in_trace") or 0)
    if steps <= 0:
        return None
    seconds = scope_share._table(obs, modules)["layers"].get(scope, 0.0)
    if seconds <= 0:
        return None
    count = getattr(importlib.import_module(f"benchmark.{bytes_module}"),
                    bytes_fn)
    return rooflines.roofline_share(count(obs["config"], obs["chips"]),
                                    seconds / steps,
                                    peaks.peaks_of(obs["device_kind"]))
