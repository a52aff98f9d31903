"""The bank-transfer cell (`bank-transfer-128k`, Savina's Bank Transaction)
at a toy size on the CPU: end to end with the controls on the CPU's slots
family, traced on the chip's, each control failing by the limits named for
it and no other; a program without the configuration's builder failing at
once; and the rooflines' byte counts against a hand count."""

import json
import os
import time

import pytest

import bench_tiny
from benchmark import harness, peaks, rooflines_transfer, xplane
from benchmark.harness import BENCH, load_json
from benchmark.reference import bank_transfer_controls as controls

CELL = "bank-transfer-128k"
CONFIG = "benchmark/configs/savina-bank-transfer-128k.json"
CONF = load_json(BENCH, "configs", "savina-bank-transfer-128k.json")
LIMITS = CONF["limits"]


@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    monkeypatch.setattr(xplane, "load",
                        bench_tiny.load_cpu_trace_as_device(xplane.load))
    return bench_tiny.tiny_root(tmp_path)


def execute(root, trace=False, seed=2 ** 31 + 99, faults=None):
    return harness.execute(CELL, seed, 1.0, trace, time.monotonic(),
                           require_chip=False, root=root, faults=faults)


def test_cell_and_its_controls_at_toy_size(root):
    # chunks of 12 steps, so that each control finds a place to plant
    bench_tiny._shrink(root, "benchmark/traffic/bank-transfers.json",
                       {"chunk_steps": 12})
    res = execute(root, faults={"controls": True})
    assert res["correct"] is True, res["compared"]
    assert set(res["compared"]) == set(LIMITS) and len(LIMITS) == 15
    assert all(c["value"] == 0 for c in res["compared"].values())
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tells_per_s", "setup_s"}
    seen = res["setup_parts_s"]["transfer"]
    # the budget retained mail, a stash held 4 or more, a row filled K_s
    assert seen["retained_by_budget"] > 0 and seen["stash_high_water"] >= 4
    assert seen["emit_high_water"] == CONF["emit_width"]
    assert seen["stash_overflow"] == 0
    for name, numbers in res["controls"].items():
        if name == "reference_itself":
            assert all(c["value"] == 0 for c in numbers.values()), numbers
        else:
            wrong = {k for k, c in numbers.items() if c["value"] > 0}
            assert controls.caught_as_named(name, numbers), (name, wrong)
    assert set(res["controls"]) == set(controls.CONTROLS) | {
        "reference_itself"}


def test_traced_run_on_the_chips_family_reports_every_metric(root, capfd):
    bench_tiny._shrink(root, CONFIG,
                       {"builder_args": {"delivery_backend": "reference"}})
    res = execute(root, trace=True)
    assert res["correct"] is True, res["compared"]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    mine = {m["name"] for m in man["per_layer"] if CELL in m["workloads"]}
    assert set(res["metrics"]) == mine and len(mine) == 11
    values = {k: v["value"] for k, v in res["metrics"].items()}
    for block in ("place", "emit", "retain"):
        assert values[f"xfer_{block}_share"] > 0
    assert values["xfer_emit_share"] + values["xfer_retain_share"] < 100
    assert 0 < values["xfer_stash_share"] < 100
    assert values["xfer_step_ms"] > 0 and values["xfer_emit_roofline"] > 0
    table = capfd.readouterr().err
    for block in ("akka.deliver.sort", "akka.deliver.place",
                  "akka.behavior.account", "akka.emit.slots",
                  "akka.emit.retain", "akka.emit.spill"):
        assert f"    {block} " in table, block
    assert "    akka.deliver.spill " not in table  # retention took it over


def test_a_program_without_the_builder_fails_at_once(root, monkeypatch):
    from akka_tpu.models import baseline_benches as bb
    monkeypatch.delattr(bb, "build_bank_transfer")
    t0 = time.monotonic()
    with pytest.raises(harness.BenchError, match="no builder"):
        execute(root)
    assert time.monotonic() - t0 < 30


def test_rooflines_count_by_hand():
    step = rooflines_transfer.xfer_step_bytes(CONF)
    hand = (131072 * 32 * 2 + 131072 * (16 + 12) + 659464 * 4
            + 76500 * (24 + 21) * 2)
    assert step == hand
    assert rooflines_transfer.xfer_emit_bytes(CONF) == (
        131072 * 4 + 131072) * 24
    assert CONF["inbox_rows"] == CONF["spill_capacity"] \
        + CONF["accounts"] * CONF["emit_width"] + CONF["tellers"] + 8
