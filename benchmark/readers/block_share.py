"""One block's share of the device's busy time: self time of the step
program's operations whose deepest `akka.` scope is `block` (say
`akka.deliver.max`) under the layer `layer`, over the busy time of the traced
stretch, fullest device, in percent. `scope_share` sums by layer only; this
reads one row of the same table. A program with no such block: nothing to
read."""

from __future__ import annotations

from benchmark.readers import scope_share


def read(obs, modules, layer, block):
    table = scope_share._table(obs, modules)
    seconds = sum(row[2] for row in table["rows"]
                  if row[0] == layer and row[1] == block)
    if table["busy"] <= 0 or seconds <= 0:
        return None
    return 100.0 * seconds / table["busy"]
