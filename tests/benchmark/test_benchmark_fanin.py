"""The fan-in aggregator's cell (`fanin-1m-1k`) at a toy size on the CPU:
end to end, untraced and traced, under the CPU's own delivery and under the
kernel the chip runs; the reference's closed form against its literal steps;
the controls; faults planted under the timed path, each coming out as not
correct; and the roofline's byte count against a hand count."""

import json
import os
import time

import numpy as np
import pytest

import bench_tiny
from benchmark import harness, peaks, rooflines_fanin, xplane
from benchmark.harness import BENCH, load_json
from benchmark.reference import fanin, fanin_controls

CELL = "fanin-1m-1k"
CONFIG = "benchmark/configs/fanin-aggregator-1m.json"
TRAFFIC = load_json(BENCH, "traffic", "fanin-tick.json")
LIMITS = load_json(BENCH, "configs", "fanin-aggregator-1m.json")["limits"]
# the CPU's `auto` is scatter; the chip's is the wide merge, asked for by name
DELIVERIES = {"auto": {},
              "merge-wide": {"delivery": "merge",
                             "delivery_backend": "reference"}}


@pytest.fixture()
def root(tmp_path, monkeypatch):
    # as tests/benchmark/test_benchmark_cells.py: the CPU is lent a row of
    # peaks, and its operations are made into a device plane
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    monkeypatch.setattr(xplane, "load",
                        bench_tiny.load_cpu_trace_as_device(xplane.load))
    return bench_tiny.tiny_root(tmp_path)


def execute(root, trace=False, seed=2 ** 31 + 77, faults=None, seconds=1.0):
    return harness.execute(CELL, seed, seconds, trace, time.monotonic(),
                           require_chip=False, root=root, faults=faults)


def with_delivery(root, delivery):
    if DELIVERIES[delivery]:
        bench_tiny._shrink(root, CONFIG,
                           {"builder_args": DELIVERIES[delivery]})


def verdict(numbers):
    return all(c["value"] <= c["limit"] for c in numbers.values())


# ------------------------------------------------------------- the cell
@pytest.mark.parametrize("delivery", sorted(DELIVERIES))
def test_cell_end_to_end_at_toy_size(root, delivery):
    with_delivery(root, delivery)
    res = execute(root)
    assert res["correct"] is True, res["compared"]
    assert set(res["compared"]) == set(LIMITS)
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["attempted"] % 2048 == 0  # whole steps of one tell a leaf
    assert set(res["metrics"]) == {"tells_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == 1


@pytest.mark.parametrize("delivery", sorted(DELIVERIES))
def test_traced_run_reports_every_fanin_metric(root, delivery):
    with_delivery(root, delivery)
    res = execute(root, trace=True)
    assert res["correct"] is True, res["compared"]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    mine = {m["name"] for m in man["per_layer"] if CELL in m["workloads"]}
    assert set(res["metrics"]) == mine and len(mine) == 7
    assert all(name.startswith("fanin_") for name in mine)
    shares = {k: v["value"] for k, v in res["metrics"].items()
              if k.endswith(("_roofline", "_share"))}
    assert len(shares) == 6
    assert all(0 < s <= 100 for s in shares.values()), shares
    assert shares["fanin_max_share"] <= shares["fanin_deliver_share"]
    assert res["metrics"]["fanin_step_ms"]["value"] > 0


def test_scope_table_has_the_max_block_and_a_block_per_behavior(root, capfd):
    """Which fusion carries which member's path is the compiler's choice
    (tests/test_device_scopes.py reads every instruction's): the table of a
    CPU run shows the max block and at least one behavior's."""
    execute(root, trace=True)
    table = capfd.readouterr().err
    assert "    akka.deliver.max " in table
    assert "    akka.behavior.leaf " in table \
        or "    akka.behavior.collector " in table


# -------------------------------------------------------- the reference
def leaves_of(n_leaves=600, n_collectors=7, seed=11):
    return fanin.seed_leaves(n_leaves, n_collectors, seed, TRAFFIC)


@pytest.mark.parametrize("t", [0, 1, 2, 15, 16, 17, 37, 100])
def test_closed_form_equals_the_literal_steps(t):
    leaves = leaves_of()
    inbox = (np.zeros(0, np.int64), np.zeros((0, 4), np.int64))
    state = fanin.zero_state(7)
    for s in range(t):
        inbox, state = fanin.step(s, inbox, state, leaves, TRAFFIC)
    want, told = fanin.after(t, leaves, TRAFFIC, 7)
    for k in fanin.COLLECTOR_COLUMNS:
        assert (state[k] == want[k]).all(), k
    if t == 0:
        assert told is None and inbox[0].size == 0
    else:
        assert (inbox[0] == leaves["collector"]).all()
        assert (inbox[1] == told).all()


def test_the_rule_makes_every_inbox_field_informative():
    leaves = leaves_of(1 << 16, 100, 3)
    agg = fanin.phase_aggregates(leaves, TRAFFIC, 100)
    assert fanin.period(TRAFFIC) == 16 and agg["msgs"].shape == (16, 100)
    assert len(set(agg["msgs"][0])) > 1          # count: between collectors
    assert len(set(agg["sum1"][0])) > 1          # column 1: between collectors
    assert (agg["sum1"][0] == agg["sum1"][5]).all()  # ... not between steps
    assert (agg["sum2"][0] != agg["sum2"][1]).any()  # column 2: between steps
    tops = agg["top"]
    assert (tops == 0).any() and (tops > 0).any() and tops.max() <= 7
    capable = (leaves["alarm_level"] > 0).mean()
    assert 1 / 80 < capable < 1 / 50             # one leaf in 64


def test_readings_keep_every_step_total_exact_in_f32():
    leaves = fanin.seed_leaves(1 << 20, 1000, 9, TRAFFIC)
    for t in range(16):
        told = fanin.emissions(t, leaves, TRAFFIC)
        assert told.min() >= 0 and told.max() <= 7 and (told[:, 0] == 1).all()
        assert told.sum(axis=0).max() < 2 ** 24
    other = fanin.seed_leaves(1 << 20, 1000, 10, TRAFFIC)
    assert (other["collector"] != leaves["collector"]).any()


def test_totals_are_compared_modulo_two_to_the_32():
    leaves = leaves_of()
    t = 50_000_000  # sum0 of a collector passes 2^31 here
    got = fanin_controls.reference_outcome(t, leaves, TRAFFIC, 7)
    assert max(got["collectors"]["sum0"]) >= 2 ** 31
    got["collectors"] = {k: v.astype(np.uint32).astype(np.int32)  # wrapped
                         for k, v in got["collectors"].items()}
    assert min(got["collectors"]["sum0"]) < 0
    assert verdict(fanin.judge(t, leaves, TRAFFIC, 7, got, LIMITS))
    got["collectors"]["sum0"][2] += 1
    assert not verdict(fanin.judge(t, leaves, TRAFFIC, 7, got, LIMITS))


def test_inbox_is_compared_as_a_multiset_whatever_its_layout():
    leaves = leaves_of()
    got = fanin_controls.reference_outcome(37, leaves, TRAFFIC, 7)
    order = np.random.default_rng(0).permutation(600)
    pad = np.zeros(5, bool)
    got["inbox_dst"] = np.concatenate([got["inbox_dst"][order], [-1] * 5])
    got["inbox_payload"] = np.concatenate(
        [got["inbox_payload"][order], np.full((5, 4), np.nan, np.float32)])
    got["inbox_valid"] = np.concatenate([got["inbox_valid"][order], pad])
    assert verdict(fanin.judge(37, leaves, TRAFFIC, 7, got, LIMITS))
    got["inbox_valid"][-1] = True  # a sixth message, and not a reading
    numbers = fanin.judge(37, leaves, TRAFFIC, 7, got, LIMITS)
    assert numbers["tokens_wrong"]["value"] == 1


# ---------------------------------------------------------- the controls
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_in_the_programs_place_is_correct(seed):
    leaves = leaves_of(2048, 16, seed)
    out = fanin_controls.judge_controls(37, leaves, TRAFFIC, 16, LIMITS)
    assert verdict(out["reference_itself"])
    assert set(out) == set(fanin_controls.CONTROLS) | {"reference_itself"}


WHAT_CATCHES = {"tell_lost": "tokens_wrong",
                "tell_delivered_twice": "collectors_wrong",
                "reading_to_the_neighbour": "tokens_wrong",
                "alarm_missed_by_the_max": "collectors_wrong",
                "max_of_the_step_before": "collectors_wrong",
                "drop_counted": "messages_dropped",
                "leaf_rewired": "leaves_wrong"}


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("control", sorted(fanin_controls.CONTROLS))
def test_control_with_one_guarantee_broken_is_not_correct(control, seed):
    leaves = leaves_of(2048, 16, seed)
    numbers = fanin_controls.judge_controls(
        37, leaves, TRAFFIC, 16, LIMITS)[control]
    assert not verdict(numbers)
    wrong = {k for k, c in numbers.items() if c["value"] > c["limit"]}
    assert wrong == {WHAT_CATCHES[control]}  # by one of the limits, not each


def test_controls_refuse_a_run_too_short_to_break():
    with pytest.raises(ValueError, match="three steps"):
        fanin_controls.judge_controls(2, leaves_of(), TRAFFIC, 7, LIMITS)


def test_control_tool_runs_the_fanin_controls_unchanged(root):
    res = execute(root, faults={"controls": True})
    assert res["correct"] is True
    for name, numbers in res["controls"].items():
        assert verdict(numbers) == (name == "reference_itself"), name


# ------------------------------------------------ faults under the timed path
def state_unchanged(system):
    real, calls = system.run, []

    def run(k):
        calls.append(k)
        if len(calls) != 2:
            real(k)
        else:  # one chunk counts its steps and returns the state as it was
            system.step_count = system.step_count + k
    system.run = run


def every_second_tell_left_out(system):
    real, calls = system.run, []

    def run(k):
        real(k)
        calls.append(k)
        if len(calls) == 1:  # once, of the tells then waiting
            system.inbox_valid = system.inbox_valid.at[::2].set(False)
    system.run = run


def reading_altered(system):
    system.state["reading_a"] = system.state["reading_a"].at[40].add(1)


def inbox_max_zeroed(system):
    system._core.need_max = False  # read while the step program is traced


@pytest.mark.parametrize("delivery", sorted(DELIVERIES))
@pytest.mark.parametrize("fault,caught_by", [
    (state_unchanged, {"collectors_wrong", "tokens_wrong"}),
    (every_second_tell_left_out, {"collectors_wrong"}),
    (reading_altered, {"collectors_wrong", "leaves_wrong", "tokens_wrong"}),
    (inbox_max_zeroed, {"collectors_wrong"})])
def test_fault_comes_out_as_not_correct(root, delivery, fault, caught_by):
    with_delivery(root, delivery)
    res = execute(root, faults={"fanin_step": fault})
    assert res["correct"] is False
    wrong = {k for k, c in res["compared"].items() if c["value"] > c["limit"]}
    assert wrong & caught_by, res["compared"]


def test_rule_stated_twice_has_to_agree(root):
    bench_tiny._shrink(root, "benchmark/traffic/fanin-tick.json",
                       {"alarm_period": 8})
    with pytest.raises(ValueError, match="alarm_period"):
        execute(root)


# ---------------------------------------------------------- the roofline
def test_fanin_step_bytes_against_a_hand_count():
    conf = load_json(BENCH, "configs", "fanin-aggregator-1m.json")
    # 1,048,576 leaves: 20 B of state read, a 24 B message written and read;
    # 1,000 collectors: 32 B of state read and written
    assert rooflines_fanin.fanin_step_bytes(conf) == \
        1048576 * (20 + 2 * 24) + 1000 * 2 * 32 == 71_367_168
    assert rooflines_fanin.fanin_step_bytes(conf, 4) == 71_367_168 / 4
    assert conf["state_bytes_per_leaf"] == 4 * len(fanin.LEAF_COLUMNS)
    assert conf["state_bytes_per_collector"] == 4 * len(
        fanin.COLLECTOR_COLUMNS)


def test_configuration_builds_the_deployment_the_issue_names():
    conf = load_json(BENCH, "configs", "fanin-aggregator-1m.json")
    args = conf["builder_args"]
    assert (args["n_leaves"], args["n_collectors"]) == (1 << 20, 1000)
    assert args["static"] is False and args["delivery"] == "auto"
    assert conf["reduced"] == [] and set(conf["limits"].values()) == {0}
    assert (TRAFFIC["reading_levels"], TRAFFIC["alarm_period"],
            TRAFFIC["alarm_one_in"]) == (8, 16, 64)
