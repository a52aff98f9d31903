"""Each cell end to end at a toy size on the CPU, through everything but the
harness's look for a chip; the faults a cell can have, planted under the
timed path, each coming out as not correct; a cell, a configuration, a
traffic mix, a metric and a reader added as new files and entries only; and
the command refusing to measure without a TPU."""

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import bench_tiny
from benchmark import harness, peaks, xplane

REPO = bench_tiny.REPO


@pytest.fixture()
def root(tmp_path, monkeypatch):
    # the toy runs happen on the CPU backend, which has no published peaks:
    # lend it a row so the roofline reader's arithmetic is driven too
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    # ... and writes no device plane: the tests' own loader makes one of its
    # operations, so that the reduction's whole path is driven
    monkeypatch.setattr(xplane, "load",
                        bench_tiny.load_cpu_trace_as_device(xplane.load))
    return bench_tiny.tiny_root(tmp_path)


def execute(root, cell, trace=False, seed=2 ** 31 + 99, faults=None,
            seconds=1.0):
    return harness.execute(cell, seed, seconds, trace, time.monotonic(),
                           require_chip=False, root=root, faults=faults)


def assert_result_shape(res, e2e):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"  # the numbers compared come last
    assert set(res["metrics"]) == set(e2e)
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in res["compared"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("cell", ["ring-dynamic-1m", "xshard-ring-4chip"])
def test_cell_end_to_end_at_toy_size(root, cell):
    res = execute(root, cell)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert_result_shape(res, {"tells_per_s", "setup_s"})
    chips = 4 if cell == "xshard-ring-4chip" else 1
    assert res["device"]["count"] == chips


@pytest.mark.parametrize("cell", ["ring-dynamic-1m", "xshard-ring-4chip"])
def test_traced_run_reports_the_cells_per_layer_metrics(root, cell):
    res = execute(root, cell, trace=True)
    assert res["correct"] is True, res["compared"]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    mine = {m["name"] for m in man["per_layer"] if cell in m["workloads"]}
    assert set(res["metrics"]) == mine
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and len(
        res["breakdown"]["device_ops"]) <= 10
    for name, _ in res["breakdown"]["device_ops"]:
        assert name.startswith("jit_") and "/" in name  # <module>/<op>
    for m in res["metrics"].values():
        assert m["value"] == m["value"]  # never NaN
    shares = [v["value"] for k, v in res["metrics"].items()
              if k.endswith(("_roofline", "_idle_share", "_share"))]
    assert all(0 < s <= 100 for s in shares)


def test_unknown_device_kind_is_an_error_not_a_default(tmp_path, monkeypatch):
    monkeypatch.setattr(xplane, "load",
                        bench_tiny.load_cpu_trace_as_device(xplane.load))
    root = bench_tiny.tiny_root(tmp_path)  # no row lent to "cpu" here
    with pytest.raises(KeyError, match="no published peaks"):
        execute(root, "ring-dynamic-1m", trace=True)


def test_trace_without_a_device_plane_is_refused(tmp_path):
    """The yardstick itself never lets host operations stand in for a
    chip's: with `xplane.load` as it is, a CPU run's trace is refused."""
    root = bench_tiny.tiny_root(tmp_path)
    with pytest.raises(harness.BenchError, match="no device plane"):
        execute(root, "ring-dynamic-1m", trace=True)


def test_step_program_under_another_name_is_an_error_not_a_silence(root):
    """A `device_trace` metric listed for the cell that finds nothing to
    read (here: the step program's module name no longer matches) raises;
    the metric is not quietly left out of the line."""
    bench_tiny._shrink(root, "benchmark/metrics/ring_step_ms.json",
                       {"args": {"modules": ["^jit_renamed_step$"]}})
    with pytest.raises(harness.BenchError, match="ring_step_ms.*found nothing"):
        execute(root, "ring-dynamic-1m", trace=True)


# ------------------------------------------------ faults under the timed path
def ring_state_unchanged(system):
    real, calls = system.run, []

    def run(k):
        calls.append(k)
        if len(calls) != 2:  # one chunk returns the state as it was
            real(k)
    system.run = run


def ring_half_the_batch_left_out(system):
    system.inbox_valid = system.inbox_valid.at[::2].set(False)


def ring_token_altered(system):
    system.inbox_payload = system.inbox_payload.at[5, 1].add(1.0)


@pytest.mark.parametrize("fault", [ring_state_unchanged,
                                   ring_half_the_batch_left_out,
                                   ring_token_altered])
@pytest.mark.parametrize("cell", ["ring-dynamic-1m", "xshard-ring-4chip"])
def test_ring_fault_comes_out_as_not_correct(root, cell, fault):
    res = execute(root, cell, faults={"ring_step": fault})
    assert res["correct"] is False
    wrong = {k for k, c in res["compared"].items() if c["value"] > c["limit"]}
    assert wrong & {"rows_received_wrong", "tokens_wrong"}


def test_exchange_between_chips_left_out_comes_out_as_not_correct(
        root, monkeypatch):
    # the all_to_all becomes the identity: every shard keeps what it meant
    # to send, so no token reaches the chip it was told to
    monkeypatch.setattr(jax.lax, "all_to_all",
                        lambda x, *a, **kw: x)
    res = execute(root, "xshard-ring-4chip")
    assert res["correct"] is False


# ------------------------------------------------------- the harness is data
def test_a_later_pr_adds_a_cell_by_adding_files_and_entries_only(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    with open(os.path.join(root, "benchmark/configs/actors-1m.json")) as f:
        conf = json.load(f)
    conf.update(name="toy-ring", actors=512,
                builder_args={"n": 512, "static": False, "delivery": "auto"})
    bench_tiny.add_file(root, "benchmark/configs/toy-ring.json", conf)
    bench_tiny.add_file(root, "benchmark/traffic/ring-steps-of-one.json", {
        "name": "ring-steps-of-one", "generator": "ring_full",
        "tokens_per_actor": 1, "payload_max": 3, "chunk_steps": 1,
        "warm_chunks": 1, "trace_after_seconds": 0.05, "trace_seconds": 0.1})
    bench_tiny.add_file(root, "benchmark/readers/toy_obs.py",
                        "def read(obs, key):\n    return obs.get(key)\n")
    bench_tiny.add_file(root, "benchmark/metrics/toy_steps_run.json",
                        {"reader": "toy_obs", "args": {"key": "steps"}})
    bench_tiny.add_file(root, "benchmark/metrics/toy_nothing_to_read.json",
                        {"reader": "toy_obs", "args": {"key": "absent"}})
    man["configs"].append({"name": "toy-ring", "source": "a test",
                           "file": "benchmark/configs/toy-ring.json",
                           "reduced": [], "why": "a test"})
    man["workloads"].append({"name": "toy-cell", "config": "toy-ring",
                             "traffic": "ring-steps-of-one", "chips": 1,
                             "why": "a test"})
    next(m for m in man["end_to_end"]
         if m["name"] == "tells_per_s")["workloads"].append("toy-cell")
    for name in ("toy_steps_run", "toy_nothing_to_read"):
        man["per_layer"].append({
            "name": name, "unit": "steps", "better": "higher",
            "source": "program_counter", "layer": "device step",
            "moves": "tells_per_s", "workloads": ["toy-cell"]})
    next(m for m in man["per_layer"]
         if m["name"] == "ring_step_ms")["workloads"].append("toy-cell")
    bench_tiny.write_manifest(root, man)

    res = execute(root, "toy-cell")
    assert res["correct"] is True
    assert set(res["metrics"]) == {"tells_per_s", "setup_s"}
    traced = execute(root, "toy-cell", trace=True)
    # the new reader read its counter; the one with nothing to read is
    # left out of the line, not reported as 0
    assert set(traced["metrics"]) == {"toy_steps_run", "ring_step_ms"}
    assert traced["metrics"]["toy_steps_run"]["value"] >= 2


# ----------------------------------------------------- no chip, no result
def test_command_refuses_to_measure_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "ring-dynamic-1m",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_command_refuses_with_fewer_chips_than_the_cell_asks_for(monkeypatch):
    with pytest.raises(harness.BenchError, match="needs 16 chips"):
        harness.open_devices(16, require_chip=False)


def test_command_gives_no_result_where_the_program_is_absent(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "xshard-ring-4chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu",
                               PYTHONPATH=""),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
