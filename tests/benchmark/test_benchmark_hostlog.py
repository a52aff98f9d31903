"""The per-layer metrics that read the program's own host log (ISSUE 36):
each reader on a recorded log, on a log that lacks a span the program
always emits (an error), on a program that keeps no log (an honest 0); and
the toy traced run of the three cells whose own test files count their
metrics, with the five new ones listed for them in the toy manifest as a
later `benchmark` PR would list them."""

import json
import os
import time

import pytest

import bench_tiny
from akka_tpu.event import flight_recorder as fr
from benchmark import harness, peaks, xplane
from benchmark.readers import _hostlog

NEW = {"setup_build_s": "s", "setup_trace_lower_s": "s",
       "setup_compile_s": "s", "setup_cache_misses": "count",
       "run_dispatch_ms": "ms"}
MS = 1_000_000  # ns


def span(i, name, t0_ms, t1_ms, parent=0, **stats):
    return {"id": i, "parent": parent, "thread": 1, "name": name,
            "t0_ns": t0_ms * MS, "t1_ns": t1_ms * MS, "stats": stats}


def row(program, t_ms, trace_s, lower_s, compile_s, cache, span_name=None):
    return {"program": program, "t_ns": t_ms * MS, "trace_s": trace_s,
            "lower_s": lower_s, "compile_s": compile_s, "cache": cache,
            "retrieval_s": 0.0, "saved_s": 0.0, "span": span_name}


# One cell's life as the program logs it: an older system's records first
# (a process that ran another cell before), then build, a cold first chunk,
# steady chunks, and the comparison's small compile after the window.
SPANS = [
    span(1, "akka.setup.build", 0, 40, actors=8),
    span(2, "akka.device.run[16]", 50, 51, steps=16),
    span(10, "akka.setup.build", 1000, 1250, actors=1024),
    span(11, "akka.device.run[16]", 1400, 3400, steps=16, compiled=1),
    span(12, "akka.device.wait", 3400, 3500),
    span(13, "akka.device.run[16]", 3500, 3502, steps=16),
    span(14, "akka.device.run[16]", 3600, 3604, steps=16),
    span(15, "akka.device.run[16]", 3700, 3709, steps=16),
    span(16, "akka.device.run[16]", 3800, 3803, steps=16),
    span(17, "akka.device.read[state]", 4000, 4010),
]
ROWS = [
    row("jit(old)", 45, 9.0, 9.0, 9.0, "miss"),
    row("jit(zeros)", 1100, 0.01, 0.02, 0.05, "hit", "akka.setup.build"),
    row("jit(place)", 1300, 0.04, 0.06, 0.25, "miss"),
    row("jit(_run_impl)", 3390, 0.5, 0.3, 1.1, "miss", "akka.device.run[16]"),
    row("jit(gather)", 4005, 0.1, 0.1, 0.7, "miss"),  # after set-up
]


@pytest.fixture()
def recorded(monkeypatch):
    monkeypatch.setattr(fr, "span_log", lambda: [dict(r) for r in SPANS])
    monkeypatch.setattr(fr, "compile_log", lambda: [dict(r) for r in ROWS])


def read(name, obs=None):
    spec = harness.load_json(harness.BENCH, "metrics", name + ".json")
    reader = harness.load_part(harness.ROOT, "readers", spec["reader"])
    return reader.read({} if obs is None else obs, **spec.get("args", {}))


def test_each_reader_on_a_recorded_log(recorded):
    assert read("setup_build_s") == pytest.approx(0.25)  # the newest build
    assert read("setup_trace_lower_s") == pytest.approx(
        0.01 + 0.02 + 0.04 + 0.06 + 0.5 + 0.3)
    assert read("setup_compile_s") == pytest.approx(0.05 + 0.25 + 1.1)
    assert read("setup_cache_misses") == 2.0
    # the steady dispatches' median; the one that compiled is left out
    assert read("run_dispatch_ms") == pytest.approx(3.5)


def test_set_up_ends_where_the_first_steady_dispatch_begins(recorded):
    spans, rows = _hostlog.logs({})
    assert [r["id"] for r in spans] == list(range(10, 18))
    assert _hostlog.setup_end_ns(spans) == 3500 * MS
    assert [r["program"] for r in rows] == [
        "jit(zeros)", "jit(place)", "jit(_run_impl)", "jit(gather)"]


@pytest.mark.parametrize("name,gone", [
    ("setup_build_s", "akka.setup.build"),
    ("run_dispatch_ms", "akka.device.run[16]")])
def test_span_the_program_always_emits_under_another_name_is_an_error(
        monkeypatch, recorded, name, gone):
    kept = [dict(r) for r in SPANS if r["name"] != gone]
    monkeypatch.setattr(fr, "span_log", lambda: kept)
    with pytest.raises(harness.BenchError, match="holds no span"):
        read(name)


@pytest.mark.parametrize("name", ["setup_trace_lower_s", "setup_compile_s",
                                  "setup_cache_misses"])
def test_log_with_no_compile_row_is_an_error(monkeypatch, recorded, name):
    monkeypatch.setattr(fr, "compile_log", lambda: [])
    with pytest.raises(harness.BenchError, match="holds no compile row"):
        read(name)


@pytest.mark.parametrize("name", sorted(NEW))
def test_program_that_keeps_no_log_reads_an_honest_zero(monkeypatch, name):
    """The parent's program under this PR's benchmark files."""
    monkeypatch.delattr(fr, "span_log")
    assert read(name) == 0.0
    assert _hostlog.logs({}) is None


def test_readers_print_one_table_a_run(recorded, capfd):
    obs = {}
    for name in sorted(NEW):
        read(name, obs)
    err = capfd.readouterr().err
    assert err.count("host spans of the program") == 1
    assert "akka.device.run[16]" in err and "akka.setup.build" in err
    assert "jit(_run_impl)" in err and "(after set-up)" in err
    line = next(ln for ln in err.splitlines() if "akka.device.run[16]" in ln
                and "total" in ln)
    assert "n     5" in line  # this run's five, the older system's left out


def test_manifest_lists_the_five_new_metrics():
    man = harness.load_json(harness.ROOT, "BENCHMARK.json")
    mine = {m["name"]: m for m in man["per_layer"] if m["name"] in NEW}
    assert set(mine) == set(NEW)
    for name, m in mine.items():
        assert m["unit"] == NEW[name] and m["better"] == "lower"
        assert m["source"] == ("program_counter" if name.endswith("misses")
                               else "program_span")
        want = ("tells_per_s", "step driver (host)") \
            if name == "run_dispatch_ms" else ("setup_s", "set-up (host)")
        assert (m["moves"], m["layer"]) == want
        # the cells whose own tests admit a metric they do not count
        # (test_benchmark_fanin / _router / _bank count theirs: PERF.md 7.8)
        assert m["workloads"] == ["ring-dynamic-1m", "xshard-ring-4chip"]
    assert [m["name"] for m in man["per_layer"]][-5:] == [
        "setup_build_s", "setup_trace_lower_s", "setup_compile_s",
        "setup_cache_misses", "run_dispatch_ms"]


# ------------------------------------------- the other three cells, at toy size
@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    monkeypatch.setattr(xplane, "load",
                        bench_tiny.load_cpu_trace_as_device(xplane.load))
    root = bench_tiny.tiny_root(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    for m in man["per_layer"]:  # as a later `benchmark` PR would: appended
        if m["name"] in NEW:
            m["workloads"] += ["fanin-1m-1k", "router-100k", "bank-ordered-1m"]
    bench_tiny.write_manifest(root, man)
    return root


@pytest.mark.parametrize("cell", ["fanin-1m-1k", "router-100k",
                                  "bank-ordered-1m"])
def test_traced_run_reports_the_new_metrics_in_the_other_cells(root, cell):
    fr.clear_span_log()
    res = harness.execute(cell, 2 ** 31 + 36, 1.0, True, time.monotonic(),
                          require_chip=False, root=root)
    assert res["correct"] is True, res["compared"]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    mine = {m["name"] for m in man["per_layer"] if cell in m["workloads"]}
    assert set(res["metrics"]) == mine and set(NEW) < mine
    got = {k: res["metrics"][k] for k in NEW}
    assert {k: v["unit"] for k, v in got.items()} == NEW
    assert all(v["value"] == v["value"] for v in got.values())  # never NaN
    assert got["setup_build_s"]["value"] > 0
    assert got["setup_trace_lower_s"]["value"] > 0
    assert got["setup_compile_s"]["value"] > 0
    assert got["run_dispatch_ms"]["value"] > 0
    # what the program logged is what the reader summed: one build, and the
    # step program's row among set-up's
    (build,) = [r for r in fr.span_log() if r["name"] == "akka.setup.build"]
    assert got["setup_build_s"]["value"] == pytest.approx(
        (build["t1_ns"] - build["t0_ns"]) / 1e9)
    step_rows = [r for r in fr.compile_log()
                 if r["program"] == "jit(_run_impl)"]
    assert len(step_rows) == 1
    assert got["setup_compile_s"]["value"] >= step_rows[0]["compile_s"]
