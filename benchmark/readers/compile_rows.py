"""Set-up's compile rows from the program's own compile log (one row a
program compiled or loaded before the first steady dispatch began): the sum
of the seconds under `fields` (`trace_s`, `lower_s`, `compile_s`), or with
`cache` the count of rows whose persistent-cache outcome it names (`miss`:
the entry was written, so the run compiled cold; 0 on a warm run)."""

from benchmark.readers import _hostlog


def read(obs, fields=(), cache=None):
    got = _hostlog.logs(obs)
    if got is None:
        return 0.0
    spans, rows = got
    end = _hostlog.setup_end_ns(spans)
    rows = [r for r in rows if r["t_ns"] <= end]
    if cache is not None:
        return float(sum(1 for r in rows if r["cache"] == cache))
    return float(sum(r[f] for r in rows for f in fields))
