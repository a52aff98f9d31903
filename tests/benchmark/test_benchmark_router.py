"""The router pool's cell (`router-100k`) at a toy size on the CPU: end to
end, untraced and traced, under the CPU's own delivery and under the kernel
the chip runs; the reference's folded window's end against its literal
steps; the controls, each failing by the limits named for it and no other;
faults planted under the timed path, each coming out as not correct; and the
rooflines' byte counts against a hand count."""

import json
import os
import time

import numpy as np
import pytest

import bench_tiny
from benchmark import harness, peaks, rooflines_router, xplane
from benchmark.harness import BENCH, load_json
from benchmark.reference import router, router_controls

CELL = "router-100k"
CONFIG = "benchmark/configs/router-pool-100k.json"
TRAFFIC = load_json(BENCH, "traffic", "router-random.json")
LIMITS = load_json(BENCH, "configs", "router-pool-100k.json")["limits"]
JUDGED = set(LIMITS) - {"compiles_in_window"}  # what the reference judges
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


def wrong_of(numbers):
    return {k for k, c in numbers.items() if c["value"] > c["limit"]}


# ------------------------------------------------------------- the cell
@pytest.mark.parametrize("delivery", sorted(DELIVERIES))
def test_cell_end_to_end_at_toy_size(root, delivery):
    with_delivery(root, delivery)
    res = execute(root)
    assert res["correct"] is True, res["compared"]
    assert set(res["compared"]) == set(LIMITS) and len(LIMITS) == 7
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tells_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == 1


@pytest.mark.parametrize("delivery", sorted(DELIVERIES))
def test_traced_run_reports_every_router_metric(root, delivery):
    with_delivery(root, delivery)
    res = execute(root, trace=True)
    assert res["correct"] is True, res["compared"]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    mine = {m["name"] for m in man["per_layer"] if CELL in m["workloads"]}
    assert set(res["metrics"]) == mine and len(mine) == 8
    assert all(name.startswith("router_") for name in mine)
    values = {k: v["value"] for k, v in res["metrics"].items()}
    assert values["router_route_share"] > 0
    assert values["router_route_roofline"] > 0
    assert values["router_step_ms"] > 0
    # the four layers' shares are parts of one busy time
    shares = {k: v for k, v in values.items()
              if k.endswith("_share") and "idle" not in k}
    assert len(shares) == 4 and sum(shares.values()) <= 100 + 1e-6
    assert 0 <= values["router_device_idle_share"] <= 100


def test_scope_table_has_the_route_layer_and_its_rank_block(root, capfd):
    """Which fusion carries which member's path is the compiler's choice
    (tests/test_device_scopes.py reads every instruction's): the table of a
    CPU run shows the layer and the block of its prefix count."""
    execute(root, trace=True)
    table = capfd.readouterr().err
    assert "  akka.route " in table
    assert "    akka.route.rank " in table


def test_traced_run_of_a_cell_with_no_router_reads_nothing_under_route(root):
    """The new reader on a program that lacks the scope: nothing to read,
    and no exception (a cell with no router, as every cell of the parent)."""
    from benchmark.readers import scope_roofline_of
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    bench_tiny.add_file(root, "benchmark/metrics/fanin_route_roofline.json",
                        load_json(BENCH, "metrics",
                                  "router_route_roofline.json"))
    man["per_layer"].append({
        "name": "fanin_route_roofline", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "route", "moves": "tells_per_s",
        "workloads": ["fanin-1m-1k"]})
    bench_tiny.write_manifest(root, man)
    res = harness.execute("fanin-1m-1k", 5, 1.0, True, time.monotonic(),
                          require_chip=False, root=root)
    assert res["correct"] is True
    assert "fanin_route_roofline" not in res["metrics"]
    assert "fanin_step_ms" in res["metrics"]
    assert scope_roofline_of.read({"steps_in_trace": 0}, [], "akka.route",
                                  "rooflines_router",
                                  "router_route_bytes") is None


# -------------------------------------------------------- the reference
def producers_of(n_producers=600, n_routees=7, seed=11):
    return router.seed_producers(n_producers, n_routees, seed, TRAFFIC)


def literal(t, producers, n, logic):
    routees, counter = router.zero_state(n)
    inbox = np.zeros((0, 4), np.int64)
    for s in range(t):
        inbox, routees, counter = router.step(s, inbox, routees, counter,
                                              producers, TRAFFIC, logic)
    return inbox, routees, counter


@pytest.mark.parametrize("n_routees", [7, 64, 1000])
@pytest.mark.parametrize("t", [0, 1, 2, 15, 16, 17, 37, 100])
def test_folded_end_equals_the_literal_steps(t, n_routees):
    """`after`'s fold-and-rotate against `step` applied t times, where the
    pool is far smaller than a step's count, about its size, and larger."""
    producers = producers_of(600, n_routees)
    inbox, routees, counter = literal(t, producers, n_routees, "round-robin")
    want, want_counter, told = router.after(t, producers, TRAFFIC, n_routees)
    for k in router.ROUTEE_COLUMNS:
        assert (routees[k] == want[k]).all(), k
    assert counter == want_counter
    if t == 0:
        assert told is None and inbox.shape[0] == 0
    else:
        assert (inbox == told).all()
    assert routees["hits"].max() - routees["hits"].min() <= 1


def test_random_logic_is_the_literal_rule_and_promises_no_balance():
    producers = producers_of(600, 64)
    _, routees, counter = literal(20, producers, 64, "random")
    want, want_counter, _ = router.after(20, producers, TRAFFIC, 64, "random")
    assert all((routees[k] == want[k]).all() for k in router.ROUTEE_COLUMNS)
    assert counter == want_counter
    assert routees["hits"].max() - routees["hits"].min() > 1
    assert routees["hits"].sum() == counter["routed"]
    with pytest.raises(ValueError, match="unknown routing logic"):
        router.index_of(np.arange(3), 7, "smallest-mailbox")
    with pytest.raises(ValueError, match="unknown routing logic"):
        router.after(3, producers, TRAFFIC, 64, "smallest-mailbox")


def test_the_traffic_is_what_the_issue_names():
    producers = router.seed_producers(1 << 16, 1000, 9, TRAFFIC)
    counts = [int(router.tells(t, producers, TRAFFIC).sum())
              for t in range(32)]
    assert all(abs(c / (1 << 16) - 0.5) < 0.01 for c in counts)  # one half
    assert counts[:16] == counts[16:] and len(set(counts[:16])) > 1
    a, b = (router.tells(t, producers, TRAFFIC) for t in (3, 4))
    assert 0.4 < (a != b).mean() < 0.6  # another half each step
    assert (router.tells(3, producers, TRAFFIC)
            == router.tells(19, producers, TRAFFIC)).all()
    told = router.emissions(5, producers, TRAFFIC)
    assert (told[:, 0] == 1).all() and told.min() >= 0 and told.max() == 7
    assert (producers["router"] == 1000).all()
    other = router.seed_producers(1 << 16, 1000, 10, TRAFFIC)
    assert (other["mask"] != producers["mask"]).any()


def test_totals_are_compared_modulo_two_to_the_32():
    """A window of 48 s routes 3.7e9 tells: `routed` passes 2^31 (and 2^32)
    and the system's int32 columns wrap."""
    producers = producers_of(600, 7)
    args = (50, producers, TRAFFIC, 7, "round-robin")
    got = router_controls.reference_outcome(*args)
    for k in router.TOTALS:  # as columns that wrapped once, twice, ...
        got["routees"][k] = (got["routees"][k] + 2 ** 31) % 2 ** 32 - 2 ** 31 \
            - 2 ** 32 * np.arange(7)
    got["router"]["routed"] -= 2 ** 32
    assert verdict(router.judge(*args, got, LIMITS))
    got["routees"]["sum1"][2] += 1
    assert wrong_of(router.judge(*args, got, LIMITS)) == {"routees_wrong"}
    got["routees"]["sum1"][2] -= 1
    got["router"]["routed"] += 1
    assert wrong_of(router.judge(*args, got, LIMITS)) == {
        "router_counter_wrong"}


def test_inbox_is_compared_as_a_multiset_whatever_its_layout():
    producers = producers_of()
    args = (37, producers, TRAFFIC, 7, "round-robin")
    got = router_controls.reference_outcome(*args)
    m = got["inbox_dst"].shape[0]
    order = np.random.default_rng(0).permutation(m)
    got["inbox_dst"] = np.concatenate([got["inbox_dst"][order], [-1] * 5])
    got["inbox_payload"] = np.concatenate(
        [got["inbox_payload"][order], np.full((5, 4), np.nan, np.float32)])
    got["inbox_valid"] = np.concatenate([got["inbox_valid"][order],
                                         np.zeros(5, bool)])
    assert verdict(router.judge(*args, got, LIMITS))
    # a message already numbered (addressed to a routee, not to the router's
    # ref) is not what a window leaves: the stage runs at the head of a step
    got["inbox_dst"][0] = 3
    assert router.judge(*args, got, LIMITS)["tokens_wrong"]["value"] == 2


# ---------------------------------------------------------- the controls
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_in_the_programs_place_is_correct(seed):
    producers = producers_of(2048, 200, seed)
    out = router_controls.judge_controls(37, producers, TRAFFIC, 200,
                                         "round-robin", LIMITS)
    assert verdict(out["reference_itself"])
    assert set(out["reference_itself"]) == JUDGED
    assert set(out) == set(router_controls.CONTROLS) | {"reference_itself"}
    assert set(router_controls.CAUGHT_BY) == set(router_controls.CONTROLS)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("control", sorted(router_controls.CONTROLS))
def test_control_with_one_guarantee_broken_is_not_correct(control, seed):
    producers = producers_of(2048, 200, seed)
    numbers = router_controls.judge_controls(
        37, producers, TRAFFIC, 200, "round-robin", LIMITS)[control]
    assert not verdict(numbers)
    must, may = router_controls.CAUGHT_BY[control]
    assert must <= wrong_of(numbers) <= must | may  # and by no other
    assert router_controls.caught_as_named(control, numbers)


def test_every_limit_is_tripped_by_some_control_that_must():
    named = set().union(*(must for must, _ in
                          router_controls.CAUGHT_BY.values()))
    assert named == JUDGED


def test_controls_refuse_a_run_they_cannot_break():
    with pytest.raises(ValueError, match="three steps"):
        router_controls.judge_controls(2, producers_of(), TRAFFIC, 7,
                                       "round-robin", LIMITS)
    with pytest.raises(ValueError, match="round-robin"):
        router_controls.judge_controls(9, producers_of(), TRAFFIC, 7,
                                       "random", LIMITS)


def test_control_tool_runs_the_router_controls(root):
    res = execute(root, faults={"controls": True})
    assert res["correct"] is True
    for name, numbers in res["controls"].items():
        if name == "reference_itself":
            assert verdict(numbers)
        else:
            assert router_controls.caught_as_named(name, numbers), name


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


def job_altered(system):
    system.state["size"] = system.state["size"].at[240].add(1)


def counter_reset(system):
    real, calls = system.run, []

    def run(k):
        real(k)
        calls.append(k)
        if len(calls) == 1:  # the pool starts over once
            system.state["next"] = system.state["next"] * 0
    system.run = run


def stage_left_out(system):
    system._core.routers = ()  # read while the step program is traced


def inbox_max_zeroed(system):
    system._core.need_max = False  # read while the step program is traced


@pytest.mark.parametrize("delivery", sorted(DELIVERIES))
@pytest.mark.parametrize("fault,caught_by", [
    (state_unchanged, {"routees_wrong", "router_counter_wrong"}),
    (every_second_tell_left_out, {"routees_wrong", "router_counter_wrong"}),
    (job_altered, {"routees_wrong", "producers_wrong", "tokens_wrong"}),
    (counter_reset, {"routees_wrong", "router_counter_wrong",
                     "balance_over_one"}),
    (stage_left_out, {"routees_wrong", "router_counter_wrong"}),
    (inbox_max_zeroed, {"routees_wrong"})])
def test_fault_comes_out_as_not_correct(root, delivery, fault, caught_by):
    with_delivery(root, delivery)
    res = execute(root, faults={"router_step": fault})
    assert res["correct"] is False
    assert wrong_of(res["compared"]) & caught_by, res["compared"]


def test_rule_stated_twice_has_to_agree(root):
    bench_tiny._shrink(root, "benchmark/traffic/router-random.json",
                       {"mask_period": 8})
    with pytest.raises(ValueError, match="mask_period"):
        execute(root)


def test_inbox_rows_stated_have_to_be_the_builders(root):
    bench_tiny._shrink(root, CONFIG, {"inbox_rows": 9999})
    with pytest.raises(ValueError, match="inbox_rows"):
        execute(root)


# --------------------------------------------------------- the rooflines
def test_router_bytes_against_a_hand_count():
    conf = load_json(BENCH, "configs", "router-pool-100k.json")
    # 1,048,576 producers: 20 B of state read; 100,000 routees: 28 B read
    # and written; the router's 8 B read and written; 524,288 telling
    # producers: a 24 B message written and read, its 4 B address read and
    # rewritten by the stage
    assert rooflines_router.router_step_bytes(conf) == \
        1048576 * 20 + 100000 * 2 * 28 + 2 * 8 + 524288 * (2 * 24 + 8) \
        == 55_931_664
    # the stage: the 4 B address of each of 1,148,585 inbox rows, read and
    # written
    assert rooflines_router.router_route_bytes(conf) == 1148585 * 8
    assert conf["inbox_rows"] == 1048576 + 100000 + 1 + 8
    assert conf["state_bytes_per_producer"] == 4 * len(
        router.PRODUCER_COLUMNS)
    assert conf["state_bytes_per_routee"] == 4 * len(router.ROUTEE_COLUMNS)
    assert conf["state_bytes_router"] == 4 * len(router.ROUTER_COLUMNS)


def test_configuration_builds_the_deployment_the_issue_names():
    conf = load_json(BENCH, "configs", "router-pool-100k.json")
    args = conf["builder_args"]
    assert (args["n_producers"], args["n_routees"]) == (1 << 20, 100_000)
    assert args["logic"] == "round-robin" and args["delivery"] == "auto"
    assert conf["reduced"] == [] and set(conf["limits"].values()) == {0}
    assert len(conf["source"]) <= 200 and len(conf["guarantees"]) == 4
    assert (TRAFFIC["tell_one_in"], TRAFFIC["mask_period"],
            TRAFFIC["levels"], TRAFFIC["chunk_steps"]) == (2, 16, 8, 32)
