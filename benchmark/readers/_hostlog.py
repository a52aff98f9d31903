"""What the readers of the program's own host log share.

The program (`akka_tpu.event.flight_recorder`) keeps one log a process:
`span_log()`, every `trace_span` as a record with its name, start and end on
`time.monotonic_ns()` and stats, and `compile_log()`, one row a program that
was compiled or loaded. A run of the benchmark is one cell a process, so the
log is the run's. Set-up is what ended before the first STEADY dispatch
began (the first `akka.device.run[n]` with no compilation inside it): the
warm chunks' compile is in, the timed window and the comparison after it
are out. The log is a ring: a run that dispatches more often than the ring
is long loses its oldest records, the set-up first, and the reader says so.

A program that keeps no such log (one from before it) reads an honest 0
under every one of these metrics. A program that keeps one and has no
record of a span it always emits runs under other names than the metric
looks for: that is an error, not a silence."""

from __future__ import annotations

import re
import statistics
import sys

from benchmark import harness

BUILD_SPAN = r"^akka\.setup\.build$"
RUN_SPAN = r"^akka\.device\.run\["


def logs(obs: dict):
    """(spans, compile rows) of the run, or None where the program keeps no
    log; read once a run, kept in `obs`, and printed as a table then. The
    run is the newest system's life: what ended after the last
    `akka.setup.build` began (the whole log where none is in it), so that a
    process which runs cell after cell, as the tests do, reads its newest."""
    if "host_log" in obs:
        return obs["host_log"]
    try:
        from akka_tpu.event import flight_recorder as fr
    except ImportError:
        fr = None
    if not hasattr(fr, "span_log") or not hasattr(fr, "compile_log"):
        obs["host_log"] = None
        return None
    spans, rows = fr.span_log(), fr.compile_log()
    if not rows:
        raise missing("compile row")
    builds = named(spans, BUILD_SPAN)
    since = builds[-1]["t0_ns"] if builds else 0
    spans = [r for r in spans if r["t1_ns"] >= since]
    rows = [r for r in rows if r["t_ns"] >= since]
    _print_table(spans, rows)
    obs["host_log"] = spans, rows
    return obs["host_log"]


def seconds(rec: dict) -> float:
    return (rec["t1_ns"] - rec["t0_ns"]) / 1e9


def steady(rec: dict) -> bool:
    return not rec["stats"].get("compiled", 0)


def named(spans, pattern: str) -> list:
    return [r for r in spans if re.search(pattern, r["name"])]


def setup_end_ns(spans) -> float:
    """Start of the first steady dispatch; the end of time where none ran."""
    starts = [r["t0_ns"] for r in named(spans, RUN_SPAN) if steady(r)]
    return min(starts, default=float("inf"))


def missing(what: str):
    return harness.BenchError(
        f"the program's host log holds no {what}: the span or compile "
        f"event it always emits runs under another name, or fell out of "
        f"the ring")


def _print_table(spans, rows) -> None:
    """To stderr beside the scope table: every span name with its count,
    total, median and maximum, then the compile rows."""
    by_name = {}
    for r in spans:
        if r["name"].startswith("akka."):
            by_name.setdefault(r["name"], []).append(seconds(r))
    lines = [f"host spans of the program ({len(spans)} records in the log; "
             f"set-up ends with the first steady dispatch):"]
    for name, secs in sorted(by_name.items(), key=lambda kv: -sum(kv[1])):
        lines.append(f"  {name:<28} n {len(secs):5d}  total {sum(secs):9.4f} s"
                     f"  median {1e3 * statistics.median(secs):9.4f} ms"
                     f"  max {1e3 * max(secs):9.4f} ms")
    end = setup_end_ns(spans)
    lines.append(f"programs compiled or loaded ({len(rows)} rows):")
    for r in sorted(rows, key=lambda r: -r["compile_s"])[:12]:
        lines.append(
            f"  {r['program']:<34} trace {r['trace_s']:7.3f} s  lower "
            f"{r['lower_s']:7.3f} s  compile {r['compile_s']:8.3f} s  cache "
            f"{r['cache'] or '-':<4} in {r['span'] or '-'}"
            f"{'' if r['t_ns'] <= end else '  (after set-up)'}")
    print("\n".join(lines), file=sys.stderr, flush=True)
