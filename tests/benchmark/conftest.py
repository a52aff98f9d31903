"""Toy sizes of what later PRs added to the benchmark: `bench_tiny.tiny_root`
shrinks every configuration of BENCHMARK.json through its `TINY` tables, so
a new configuration or traffic mix adds its entry here, before any test
builds a root."""

import tempfile

import pytest

import bench_tiny

bench_tiny.TINY.setdefault("fanin-aggregator-1m", {
    "builder_args": {"n_leaves": 2048, "n_collectors": 16},
    "leaves": 2048, "collectors": 16})
bench_tiny.TINY_TRAFFIC.setdefault("fanin-tick", {
    "chunk_steps": 2, "warm_chunks": 1,
    "trace_after_seconds": 0.05, "trace_seconds": 0.1})


@pytest.fixture(autouse=True)
def _temporary_files_of_its_own(tmp_path, monkeypatch):
    """A traced run keeps its trace under `<tmp>/bench_*/` and the scope
    reader finds it again by loading every trace there
    (`xscope.find_trace_file`). Two test files make traced runs now, on two
    workers: one would load a file the other is deleting. So each test's
    `<tmp>` is its own."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
