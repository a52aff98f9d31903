"""A step's share of the memory roofline: least bytes from the
configuration's shapes (benchmark/rooflines.py) over the chip's published
bandwidth (benchmark/peaks.py), over the step's device time."""

from benchmark import peaks, rooflines
from benchmark.readers._common import step_seconds


def read(obs, modules, bytes_fn):
    seconds = step_seconds(obs, modules)
    if seconds is None:
        return None
    least = getattr(rooflines, bytes_fn)(obs["config"], obs["chips"])
    return rooflines.roofline_share(least, seconds,
                                    peaks.peaks_of(obs["device_kind"]))
