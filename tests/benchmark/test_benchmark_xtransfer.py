"""The sharded bank-transfer cell (`xshard-transfer-4chip`, Savina's Bank
Transaction cluster-sharded) at a toy size on 4 of the CPU's virtual
devices: end to end with the controls on the CPU's kernel families, traced
on the chip's, each control failing by the limits named for it and no other
(after a window of the driver's, and on a run of the reference's own whose
steps are fixed); a fault planted under the timed path; a program without
the configuration's builder failing at once; the placement by hand; and the
rooflines' byte counts against a hand count."""

import json
import os
import time

import jax.numpy as jnp
import pytest

import bench_tiny
from benchmark import harness, peaks, rooflines_xtransfer, xplane
from benchmark.harness import BENCH, load_json
from benchmark.reference import bank_transfer_sharded as ref
from benchmark.reference import bank_transfer_sharded_controls as controls

CELL = "xshard-transfer-4chip"
CONFIG = "benchmark/configs/savina-bank-transfer-sharded-512k.json"
CONF = load_json(BENCH, "configs", "savina-bank-transfer-sharded-512k.json")
LIMITS = CONF["limits"]
JUDGED = {k: v for k, v in LIMITS.items() if k != "compiles_in_window"}


@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    monkeypatch.setattr(xplane, "load",
                        bench_tiny.load_cpu_trace_as_device(xplane.load))
    return bench_tiny.tiny_root(tmp_path)


def execute(root, trace=False, seed=2 ** 31 + 4501, faults=None):
    return harness.execute(CELL, seed, 1.0, trace, time.monotonic(),
                           require_chip=False, root=root, faults=faults)


def test_cell_and_its_controls_at_toy_size(root):
    res = execute(root, faults={"controls": True})
    assert res["correct"] is True, res["compared"]
    assert set(res["compared"]) == set(LIMITS) and len(LIMITS) == 16
    assert all(c["value"] == 0 for c in res["compared"].values())
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tells_per_s", "setup_s"}
    assert res["device"]["count"] == 4
    seen = res["setup_parts_s"]["xtransfer"]
    # the budget retained mail, a stash held 4 or more, a row filled its
    # budget, the pairs carried under their capacity and nothing was lost
    assert seen["retained_by_budget"] > 0 and seen["stash_high_water"] >= 4
    assert seen["emit_high_water"] == 2  # the toy's budget (tests/conftest)
    assert 0 < seen["exchange_high_water"] < seen["pair_cap"] == 96
    assert seen["stash_overflow"] == seen["exchange_dropped"] == 0
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
    mine = {m["name"] for m in man["per_layer"] if CELL in m.get(
        "workloads", ())}
    assert set(res["metrics"]) == mine and len(mine) == 12
    assert all(name.startswith("xxfer_") for name in mine)
    values = {k: v["value"] for k, v in res["metrics"].items()}
    assert values["xxfer_step_ms"] > 0
    assert all(0 <= v <= 100 for k, v in values.items()
               if k != "xxfer_step_ms")
    for name in ("deliver", "behavior", "emit", "retain", "exchange",
                 "collective", "stash"):
        assert values[f"xxfer_{name}_share"] > 0, name
    assert values["xxfer_exchange_roofline"] > 0
    assert values["xxfer_step_roofline"] > 0
    assert values["xxfer_collective_share"] \
        <= values["xxfer_exchange_share"] + 1e-6
    layers = [values[f"xxfer_{k}_share"]
              for k in ("deliver", "behavior", "exchange", "unscoped")]
    assert sum(layers) <= 100 + 1e-6
    # the answering path opens its scopes inside shard_map, beside the
    # exchange's
    table = capfd.readouterr().err
    for block in ("akka.exchange.bucket", "akka.exchange.all_to_all",
                  "akka.exchange.unpack", "akka.deliver.sort",
                  "akka.deliver.place", "akka.behavior.account",
                  "akka.behavior.teller", "akka.emit.slots",
                  "akka.emit.retain", "akka.emit.spill"):
        assert f"    {block} " in table, (block, [
            line for line in table.splitlines() if "akka." in line[:40]])
    assert "    akka.deliver.spill " not in table  # retention took it over


def test_a_fault_under_the_timed_path_is_not_correct(root):
    def one_teller_keyed_otherwise(system):
        row = int(system.layout.teller_row(3))
        system.state["key"] = system.state["key"].at[row].add(
            jnp.int32(1))

    res = execute(root, faults={"xtransfer_step": one_teller_keyed_otherwise})
    assert res["correct"] is False
    wrong = {k for k, c in res["compared"].items() if c["value"] > 0}
    assert {"tellers_wrong_at_open", "accounts_wrong_at_open"} <= wrong


def test_a_program_without_the_builder_fails_at_once(root, monkeypatch):
    from akka_tpu.models import baseline_benches as bb
    monkeypatch.delattr(bb, "build_bank_transfer_sharded")
    t0 = time.monotonic()
    with pytest.raises(harness.BenchError, match="no builder"):
        execute(root)
    assert time.monotonic() - t0 < 30


# -------------------------------------------------------- the reference
def a_run(dep, seed=11, steps=(16, 40, 16)):
    """Three readings of a run by the reference itself, laid out as the
    system lays its inbox out (a block a chip), with its counters."""
    keys = ref.seed_keys(dep.n_tellers, seed)
    world, counters, got = ref.World.start(keys, dep), ref.zero_counters(), {}
    for when, k in zip(("open", "close", "after"), steps):
        for _ in range(k):
            ref.step(world, dep, counters)
        got[when] = controls.reading_of(world, dep, counters)
    return keys, got


# chips, slots, budget, stash, spill, pair: the toy's, and capacities made
# too small so that every loss the rule counts happens
SHAPES = [(4, 16, 2, 96, 256, 96), (2, 16, 1, 96, 1, 384),
          (4, 4, 4, 2, 3, 20)]


@pytest.mark.parametrize("chips,slots,budget,stash,spill,pair", SHAPES)
def test_the_array_replay_takes_the_literal_steps(chips, slots, budget,
                                                  stash, spill, pair):
    """`replay_fast`, which `judge` replays with, leaves the world `step`
    leaves after every step, and counts what it counts."""
    dep = ref.Deployment(512, 512, chips, 8, slots, budget, stash, spill,
                         pair, 8)
    keys = ref.seed_keys(512, 3)
    literal, fast = ref.World.start(keys, dep), ref.World.start(keys, dep)
    total = ref.zero_counters()
    for _ in range(30):
        counted = ref.replay(literal, dep, 1)
        assert ref.replay_fast(fast, dep, 1) == counted, literal.t
        assert fast.accounts == literal.accounts and fast.stash == \
            literal.stash and fast.queues == literal.queues \
            and fast.rows == literal.rows, literal.t
        for k in ref.TELLER_COLUMNS:
            assert (fast.tellers[k] == literal.tellers[k]).all()
        total = ref.advance(total, counted)
    assert total["stashed"] > 0 and total["emit_high_water"] == budget
    if (slots, budget, stash, spill, pair) != (16, 2, 96, 256, 96):
        lost = ("mail_dropped",) if spill == 1 else (
            "stash_overflow", "exchange_dropped")
        assert all(total[k] > 0 for k in lost), total


@pytest.mark.parametrize("chips", [2, 4])
def test_every_control_fails_as_named_on_a_run_of_the_reference(chips):
    # the toy's sizes; a pair is provisioned for 3/2 of its step-0 load
    dep = ref.Deployment(512, 512, chips, 8, 16, 2, 96, 256,
                         3 * 512 // chips ** 2, 8)
    keys, got = a_run(dep)
    assert ref.judge(keys, dep, got, JUDGED) == {
        k: {"value": 0, "limit": 0} for k in JUDGED}
    out = controls.judge_controls(keys, dep, got, JUDGED)
    assert set(out) == set(controls.CONTROLS) | {"reference_itself"}
    for name, numbers in out.items():
        wrong = {k for k, c in numbers.items() if c["value"] > 0}
        if name == "reference_itself":
            assert not wrong
        else:
            assert controls.caught_as_named(name, numbers), (name, wrong)


def test_reference_places_ids_as_its_docstring_says():
    dep = ref.Deployment(512, 512, 4, 8, 16, 4, 96, 256, 96, 8)
    # 8 shards of 64 accounts, two shards a chip, 128 accounts and 128
    # tellers a chip: account 13 is in shard 5 on chip 2, the second of its
    # shard (13 div 8 = 1); teller 300 on chip 2, its 44th
    assert dep.rows_a_chip == 256
    assert dep.account_row[13] == 2 * 256 + 1 * 64 + 1
    assert dep.teller_row[300] == 2 * 256 + 128 + 44
    assert sorted(dep.account_row.tolist() + dep.teller_row.tolist()) \
        == list(range(1024))
    # the builder's placement is the same arithmetic, written apart
    from akka_tpu.models.baseline_benches import BankShardedLayout
    lay = BankShardedLayout(512, 512, 4, 8)
    ids = jnp.arange(512)
    assert (lay.account_row(ids) == dep.account_row).all()
    assert (lay.account_id(lay.account_row(ids)) == ids).all()
    assert dep.block_rows == 256 + 4 * 96 + 8


def test_rooflines_count_by_hand():
    chip = 4
    messages = 282_000 / chip
    exchange = messages * 24 * 4
    assert rooflines_xtransfer.xxfer_exchange_bytes(
        dict(CONF, messages_a_step=282_000)) == exchange
    step = (131072 * 32 * 2 + 131072 * (16 + 12) + 655360 * 4 + 151560 * 4
            + messages * (24 * 2 + 21 * 2) + exchange)
    assert rooflines_xtransfer.xxfer_step_bytes(
        dict(CONF, messages_a_step=282_000)) == step
    assert CONF["inbox_rows_per_chip"] == CONF["spill_capacity"] \
        + 4 * CONF["remote_capacity_per_pair"] + CONF["host_inbox"]
    assert CONF["emission_rows_per_chip"] == (
        CONF["accounts"] * CONF["emit_width"] + CONF["tellers"]) // 4
    assert CONF["sources"] == (CONF["accounts"] // 10) * 8
