"""Time in the operations whose name matches, over the device's busy time,
on the fullest device, in percent. Nothing matching: nothing to read."""

import re

from benchmark import xplane


def read(obs, pattern):
    dev = obs["trace_fullest"]
    sums = xplane.op_sums(obs["trace"], obs["trace_a"], obs["trace_b"], dev)
    busy = sum(sums.values())
    hit = sum(v for k, v in sums.items() if re.search(pattern, k))
    if busy <= 0 or hit <= 0:
        return None
    return 100.0 * hit / busy
