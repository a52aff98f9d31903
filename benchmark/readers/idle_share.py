"""1 - (union of the device's operation intervals) / (traced stretch), on
the fullest device, in percent."""

from benchmark import xplane


def read(obs):
    return xplane.idle_share(obs["trace"], obs["trace_a"], obs["trace_b"])
