"""Host time of the program's spans named by `name` (a regular expression),
from the program's own span log: `stat` `sum` of their seconds, or `median`
of the steady ones (no compilation inside), times `scale`. With `setup`,
only spans that ended before the first steady dispatch began."""

import statistics

from benchmark.readers import _hostlog


def read(obs, name, stat, scale=1.0, setup=False):
    got = _hostlog.logs(obs)
    if got is None:
        return 0.0
    spans, rows = got
    mine = _hostlog.named(spans, name)
    if setup:
        end = _hostlog.setup_end_ns(spans)
        mine = [r for r in mine if r["t1_ns"] <= end]
    if stat == "median":
        mine = [r for r in mine if _hostlog.steady(r)]
    if not mine:
        raise _hostlog.missing(f"span {name!r}")
    secs = [_hostlog.seconds(r) for r in mine]
    value = sum(secs) if stat == "sum" else statistics.median(secs)
    return scale * value
