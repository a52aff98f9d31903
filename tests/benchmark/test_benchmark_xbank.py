"""The sharded bank's cell (`xshard-bank-4chip`) at a toy size on 4 of the
CPU's virtual devices: end to end, untraced and traced, under both kernel
families; the reference over C chips against the one-chip reference (every
account the same whatever C is) and against the layout its docstring states;
the controls, each failing by the limits named for it and no other; faults
planted under the timed path, each coming out as not correct by the limit
named for it; and the rooflines' byte counts against a hand count.

Every patch of something that outlives a system (the account behavior is a
module-level object) goes through `monkeypatch`, which puts it back."""

import json
import os
import time

import numpy as np
import pytest

import bench_tiny
from benchmark import harness, peaks, rooflines_xbank, xplane
from benchmark.harness import BENCH, load_json
from benchmark.reference import bank, bank_sharded as ref
from benchmark.reference import bank_sharded_controls as controls

CELL = "xshard-bank-4chip"
CONFIG = "benchmark/configs/bank-sharded-128k.json"
TRAFFIC = load_json(BENCH, "traffic", "bank-commands-mesh.json")
CONF = load_json(BENCH, "configs", "bank-sharded-128k.json")
LIMITS = CONF["limits"]
JUDGED = set(LIMITS) - {"compiles_in_window"}  # what the reference judges
# the CPU's `auto` is the ranked family; the chip's is the wide one
FAMILIES = {"auto": {}, "wide": {"delivery_backend": "reference"}}


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


def with_family(root, family):
    if FAMILIES[family]:
        bench_tiny._shrink(root, CONFIG, {"builder_args": FAMILIES[family]})


def verdict(numbers):
    return all(c["value"] <= c["limit"] for c in numbers.values())


def wrong_of(numbers):
    return {k for k, c in numbers.items() if c["value"] > c["limit"]}


# ------------------------------------------------------------- the cell
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_cell_end_to_end_at_toy_size(root, family):
    with_family(root, family)
    res = execute(root)
    assert res["correct"] is True, res["compared"]
    assert set(res["compared"]) == set(LIMITS) and len(LIMITS) == 11
    assert all(c["value"] == 0 for c in res["compared"].values())
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tells_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == 4
    # the toy's mailboxes overflow and its pairs fill as the cell's do
    parts = res["setup_parts_s"]
    assert parts["spill"]["spilled"] > 0 < parts["spill"]["high_water"]
    assert parts["exchange"]["pair_cap"] == 96
    assert 32 < parts["exchange"]["exchange_high_water"] < 96
    assert parts["exchange"]["dropped"] == 0
    assert parts["setup_program"]["programs"] > 0


def test_traced_run_reports_exactly_the_eleven_xbank_metrics(root, capfd):
    with_family(root, "wide")
    res = execute(root, trace=True)
    assert res["correct"] is True, res["compared"]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    mine = {m["name"] for m in man["per_layer"] if CELL in m["workloads"]}
    assert set(res["metrics"]) == mine and len(mine) == 11
    assert all(name.startswith("xbank_") for name in mine)
    values = {k: v["value"] for k, v in res["metrics"].items()}
    assert values["xbank_step_ms"] > 0
    assert all(0 <= v <= 100 for k, v in values.items()
               if k != "xbank_step_ms")
    for name in ("place", "spill", "behavior", "exchange", "collective"):
        assert values[f"xbank_{name}_share"] > 0, name
    assert values["xbank_exchange_roofline"] > 0
    assert values["xbank_step_roofline"] > 0
    # blocks are parts of their layer, the layers parts of one busy time
    assert values["xbank_place_share"] + values["xbank_spill_share"] \
        <= values["xbank_deliver_share"] + 1e-6
    assert values["xbank_collective_share"] \
        <= values["xbank_exchange_share"] + 1e-6
    layers = [values[f"xbank_{k}_share"]
              for k in ("deliver", "behavior", "exchange", "unscoped")]
    assert sum(layers) <= 100 + 1e-6
    # the scope table names the mesh's blocks and the spill's write
    table = capfd.readouterr().err
    for block in ("akka.exchange.bucket", "akka.exchange.all_to_all",
                  "akka.exchange.unpack", "akka.deliver.sort",
                  "akka.deliver.place", "akka.deliver.spill",
                  "akka.behavior.account", "akka.emit.spill"):
        assert f"    {block} " in table, (block, [
            line for line in table.splitlines() if "akka." in line[:40]])


def test_the_benchmark_has_six_cells_and_the_banks_keep_their_metrics():
    man = load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    assert len(man["configs"]) == len(man["workloads"]) == 6
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4] == [
        "xshard-ring-4chip", CELL]
    count = {cell: sum(1 for m in man["per_layer"]
                       if cell in m.get("workloads", ()))
             for cell in (CELL, "bank-ordered-1m")}
    assert count == {CELL: 11, "bank-ordered-1m": 9}
    tells = [m for m in man["end_to_end"] if m["name"] == "tells_per_s"][0]
    assert tells["workloads"][-1] == CELL and tells["bound"] == 0.01


# -------------------------------------------------------- the reference
def deployment(chips=4, accounts=64, tellers=128, shards=8, slots=4,
               spill=64, pair=None):
    """Two tellers an account and four slots, as the benchmark's toy: no
    queue grows without bound, and at every step some mailboxes overflow."""
    return ref.Deployment(accounts, tellers, chips, shards, slots, spill,
                          pair or (accounts + tellers) // chips, 8)


def a_run(dep=None, seed=11, steps=(8, 24, 8)):
    """Three readings of a run by the reference itself, laid out as the
    system lays its inbox out (a block a chip)."""
    dep = dep or deployment()
    tellers = ref.seed_tellers(dep.n_tellers, dep.n_accounts, seed, TRAFFIC)
    accounts, queues = bank.zero_accounts(dep.n_accounts), \
        ref.empty_queues(dep)
    zero = dict.fromkeys(("mail_dropped", "exchange_dropped") + ref.COUNTERS,
                         0)
    got, t, device = {}, 0, zero
    for when, k in zip(("open", "close", "after"), steps):
        accounts, queues, counters = ref.replay(accounts, queues, t, k,
                                                tellers, TRAFFIC, dep)
        t += k
        device = controls._counters_after(device, counters)
        got[when] = controls._reading(accounts, queues, counters["rows"], t,
                                      device, dep, tellers)
    return tellers, dep, got


@pytest.mark.parametrize("chips", [1, 2, 4, 8])
def test_reference_gives_every_account_what_the_one_chip_reference_gives(
        chips):
    """Location transparency, of the reference itself: the same accounts by
    id as benchmark/reference/bank.py's whatever the number of chips, and
    as many commands left over; the sender named by its row of the layout."""
    dep = deployment(chips, spill=256)  # a chip's; ample on one chip too
    tellers = ref.seed_tellers(dep.n_tellers, dep.n_accounts, 5, TRAFFIC)
    accounts, queues, counters = ref.replay(
        bank.zero_accounts(64), ref.empty_queues(dep), 0, 40, tellers,
        TRAFFIC, dep)
    want, queue, carried = bank.replay(bank.zero_accounts(64),
                                       bank.empty_queue(), 0, 40, tellers,
                                       TRAFFIC, 4)
    index = np.full(dep.rows_a_chip * chips, -1)
    index[dep.teller_row] = np.arange(dep.n_tellers)
    for k in bank.ACCOUNT_COLUMNS:
        have = accounts[k] if k != "last_teller" else np.where(
            accounts["applied"] > 0, 64 + index[accounts[k]], 0)
        assert (have == want[k]).all(), k
    assert counters["spilled"] == carried.sum() > 0
    assert counters["spill_lost"] == counters["exchange_lost"] == 0
    assert sum(q["account"].shape[0] for q in queues) \
        == queue["account"].shape[0]
    assert want["rejected"].sum() > 0  # order matters here


def test_reference_lays_a_chips_queue_out_as_its_docstring_says():
    dep = deployment(4, tellers=192, spill=256)
    tellers = ref.seed_tellers(dep.n_tellers, dep.n_accounts, 7, TRAFFIC)
    accounts, queues, counters = ref.replay(
        bank.zero_accounts(64), ref.empty_queues(dep), 0, 9, tellers,
        TRAFFIC, dep)
    # placement, by hand: 8 shards of 8 accounts, two shards a chip; account
    # 13 is in shard 5 on chip 2, the second of its shard (13 div 8 = 1)
    assert dep.rows_a_chip == 64 and dep.account_chip[13] == 2
    assert dep.account_row[13] == 2 * 64 + 1 * 8 + 1
    assert dep.teller_row[100] == 2 * 64 + 16 + 100 % 48  # chip 100 div 48
    assert sorted(dep.account_row.tolist() + dep.teller_row.tolist()) \
        == list(range(256))
    told = ref.emissions(8, tellers, dep, TRAFFIC)
    for chip, queue in enumerate(queues):
        kept, *chunks = counters["rows"][chip]
        assert kept + sum(chunks) == queue["account"].shape[0]
        assert (dep.account_chip[queue["account"]] == chip).all()
        rows = dep.account_row[queue["account"]]
        assert (np.diff(rows[:kept]) >= 0).all()  # leftovers by account row
        fresh = queue["teller"][kept:]
        assert (np.diff(fresh) > 0).all()  # by the sender's global row
        assert (np.repeat(np.arange(4), chunks)
                == fresh // dep.rows_a_chip).all()  # a chunk a source chip
        mine = dep.account_chip[told["account"]] == chip
        assert (queue["account"][kept:] == told["account"][mine]).all()
    assert sum(r[0] for r in counters["rows"]) > 0


def test_reference_says_what_a_capacity_that_does_not_hold_would_lose():
    dep = deployment(4, tellers=192, pair=10, spill=2)
    tellers = ref.seed_tellers(dep.n_tellers, dep.n_accounts, 3, TRAFFIC)
    accounts, queues, counters = ref.replay(
        bank.zero_accounts(64), ref.empty_queues(dep), 0, 12, tellers,
        TRAFFIC, dep)
    assert counters["exchange_lost"] > 0 < counters["spill_lost"]
    assert counters["exchange_high_water"] > 10
    assert counters["spill_high_water"] == 2
    assert all(r[0] <= 2 and max(r[1:]) <= 10 for r in counters["rows"])
    assert accounts["applied"].sum() + sum(
        q["account"].shape[0] for q in queues) + counters["exchange_lost"] \
        + counters["spill_lost"] == 12 * dep.n_tellers


def test_judge_reads_the_accounts_where_the_reference_places_them():
    tellers, dep, got = a_run()
    numbers = ref.judge(tellers, TRAFFIC, dep, got, LIMITS)
    assert verdict(numbers) and set(numbers) == JUDGED
    # the same run read through another placement: nothing is where it was
    shifted = ref.Deployment(dep.n_accounts, dep.n_tellers, dep.chips,
                             dep.shards, dep.slots, dep.spill_capacity,
                             dep.pair_capacity, dep.host_rows, shift=1)
    wrong = wrong_of(ref.judge(tellers, TRAFFIC, shifted, got, LIMITS))
    assert {"accounts_wrong_at_open", "queue_wrong_at_open"} <= wrong


# ---------------------------------------------------------- the controls
@pytest.mark.parametrize("seed", [1, 2])
def test_reference_in_the_programs_place_is_correct(seed):
    tellers, dep, got = a_run(seed=seed)
    out = controls.judge_controls(tellers, TRAFFIC, dep, got, LIMITS)
    assert verdict(out["reference_itself"])
    assert set(out["reference_itself"]) == JUDGED
    assert set(out) == set(controls.CONTROLS) | {"reference_itself"}
    assert set(controls.CAUGHT_BY) == set(controls.CONTROLS)


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("control", sorted(controls.CONTROLS))
def test_control_with_one_guarantee_broken_is_not_correct(control, seed):
    tellers, dep, got = a_run(seed=seed)
    numbers = controls.judge_controls(tellers, TRAFFIC, dep, got,
                                      LIMITS)[control]
    assert not verdict(numbers)
    must, may = controls.CAUGHT_BY[control]
    assert must <= wrong_of(numbers) <= must | may  # and by no other
    assert controls.caught_as_named(control, numbers)


def test_every_limit_is_tripped_by_some_control_that_must():
    named = set().union(*(must for must, _ in controls.CAUGHT_BY.values()))
    assert named == JUDGED
    # the six the mesh added to the bank's
    assert {"chunks_reversed", "fresh_ahead_of_leftovers",
            "pair_cut_uncounted", "folded_commutatively",
            "placement_off_by_one_shard", "chip_spill_lost"} \
        <= set(controls.CONTROLS)


def test_controls_refuse_a_run_they_cannot_break():
    tellers, dep, got = a_run(steps=(8, 24, 1))
    with pytest.raises(ValueError, match="two steps"):
        controls.judge_controls(tellers, TRAFFIC, dep, got, LIMITS)
    # mailboxes that never overflow leave no spill to lose or to reorder
    tellers, dep, got = a_run(deployment(tellers=32, slots=16))
    with pytest.raises(controls.NothingToBreak):
        controls.judge_controls(tellers, TRAFFIC, dep, got, LIMITS)


def test_control_tool_runs_the_sharded_banks_controls(root):
    res = execute(root, faults={"controls": True})
    assert res["correct"] is True
    assert set(res["controls"]) == set(controls.CONTROLS) | {
        "reference_itself"}
    for name, numbers in res["controls"].items():
        if name == "reference_itself":
            assert verdict(numbers)
        else:
            assert controls.caught_as_named(name, numbers), name


# ------------------------------------------------ faults under the timed path
def after_the_warm_chunks(system, alter):
    """Plant `alter(system)` once, inside the window: after the chunk that
    follows the window's first reading (the toy runs two warm chunks)."""
    real, calls = system.run, []

    def run(k):
        real(k)
        calls.append(k)
        if len(calls) == 3:
            alter(system)
    system.run = run


def blocks(system, column):
    return column.reshape(system.n_shards, system.m_local, *column.shape[1:])


def chunks_of_two_source_chips_swapped(system):
    """Every chip reads the chunk of source chip 1 ahead of source chip
    0's: the delivery sees its inbox block with the two regions swapped."""
    import jax.numpy as jnp
    core, real = system._core, system._core.deliver
    sc, pc, m = system.spill_cap, system.pair_cap, system.m_local
    order = jnp.concatenate([jnp.arange(sc), sc + pc + jnp.arange(pc),
                             sc + jnp.arange(pc), jnp.arange(sc + 2 * pc, m)])

    def deliver(dst, mtype, payload, valid, *rest, **kw):
        return real(*(x[order] for x in (dst, mtype, payload, valid)),
                    *rest, **kw)
    core.deliver = deliver  # read while the step program is traced


def spill_rows_dropped(system):
    def alter(s):  # what the chips' spill regions carry, gone
        valid = blocks(s, s.inbox_valid)
        assert int(valid[:, :s.spill_cap].sum()) > 0
        s.inbox_valid = valid.at[:, :s.spill_cap].set(False).reshape(-1)
    after_the_warm_chunks(system, alter)


def exchange_drop_uncounted(system):
    def alter(s):  # one command a pair gone after the exchange, uncounted
        valid = blocks(s, s.inbox_valid)
        heads = s.spill_cap + s.pair_cap * np.arange(s.n_shards)
        assert bool(valid[:, heads].all())
        s.inbox_valid = valid.at[:, heads].set(False).reshape(-1)
    after_the_warm_chunks(system, alter)


def teller_rewired(system):
    row = system.layout.teller_row(system.layout.n_tellers - 7)
    system.state["c"] = system.state["c"].at[row].add(1)


@pytest.fixture()
def fold_reversed(monkeypatch):
    """Every mailbox folded youngest first. The account behavior is ONE
    object a process (`baseline_benches.bank_account`): the patch is the
    fixture's, and it is put back."""
    from akka_tpu.models import baseline_benches as bb
    real = bb.bank_account.receive

    def receive(state, mailbox, ctx):
        return real(state, mailbox._replace(
            types=mailbox.types[::-1], payload=mailbox.payload[::-1],
            valid=mailbox.valid[::-1]), ctx)
    monkeypatch.setattr(bb.bank_account, "receive", receive)
    yield None  # nothing to plant on the system: the program is traced so
    assert bb.bank_account.receive is receive


@pytest.mark.parametrize("fault,caught_by", [
    (chunks_of_two_source_chips_swapped,
     {"accounts_wrong", "accounts_wrong_at_open"}),
    (spill_rows_dropped, {"messages_unaccounted"}),
    (exchange_drop_uncounted, {"messages_unaccounted"}),
    (teller_rewired, {"tellers_wrong", "queue_wrong"})])
def test_fault_comes_out_as_not_correct(root, fault, caught_by):
    res = execute(root, faults={"xbank_step": fault})
    assert res["correct"] is False
    assert caught_by <= wrong_of(res["compared"]), res["compared"]


def test_fold_reversed_comes_out_as_not_correct_and_is_put_back(
        root, fold_reversed):
    res = execute(root)
    assert res["correct"] is False
    assert {"accounts_wrong", "accounts_wrong_at_open"} \
        <= wrong_of(res["compared"]), res["compared"]


def test_pair_capacity_that_does_not_hold_reads_not_correct(root):
    # Binomial(128, 1/4) a pair a step, 32 +- 5: a capacity of 30 loses mail
    bench_tiny._shrink(root, CONFIG, {
        "builder_args": {"remote_capacity_per_pair": 30},
        "remote_capacity_per_pair": 30, "inbox_rows_per_chip": 256 + 120 + 8})
    res = execute(root)
    assert res["correct"] is False
    assert wrong_of(res["compared"]) == {"messages_dropped",
                                         "exchange_dropped"}
    assert res["setup_parts_s"]["exchange"]["dropped"] \
        == res["compared"]["exchange_dropped"]["value"] > 0
    assert res["setup_parts_s"]["exchange"]["exchange_high_water"] > 30


def test_what_is_stated_twice_has_to_agree(root):
    bench_tiny._shrink(root, "benchmark/traffic/bank-commands-mesh.json",
                       {"period": 8})
    with pytest.raises(ValueError, match="period"):
        execute(root)
    bench_tiny._shrink(root, "benchmark/traffic/bank-commands-mesh.json",
                       {"period": 16})
    bench_tiny._shrink(root, CONFIG, {"logical_shards": 16})
    with pytest.raises(ValueError, match="logical_shards"):
        execute(root)
    bench_tiny._shrink(root, CONFIG, {"logical_shards": 8,
                                      "inbox_rows_per_chip": 9999})
    with pytest.raises(ValueError, match="inbox_rows_per_chip"):
        execute(root)


# --------------------------------------------------------- the rooflines
def test_xbank_bytes_against_a_hand_count():
    # a chip's 262,144 tellers: 16 B of state read; a 24 B message written
    # by the emit, read by the bucketing, written to the send buffer, read
    # and written by the receive, read by the enqueue; its 21 B slot written
    # and read by the fold; 32,768 accounts: 24 B read and written
    assert rooflines_xbank.xbank_step_bytes(CONF, 4) == \
        262144 * (16 + 6 * 24 + 2 * 21) + 32768 * 2 * 24 == 54_525_952
    # the exchange: the four of those message passes under `akka.exchange`
    assert rooflines_xbank.xbank_exchange_bytes(CONF, 4) == \
        262144 * 4 * 24 == 25_165_824
    assert CONF["slot_bytes"] == 4 + 4 * CONF["payload_width"] + 1


def test_configuration_builds_the_deployment_the_issue_names():
    args = CONF["builder_args"]
    one = load_json(BENCH, "configs", "bank-accounts-128k.json")
    for key in ("accounts", "tellers", "mailbox_slots", "payload_width",
                "payload_dtype", "period", "levels", "out_degree",
                "host_inbox", "state_bytes_per_account",
                "state_bytes_per_teller", "message_bytes", "slot_bytes"):
        assert CONF[key] == one[key], key  # one deployment, on 1 node and 4
    assert (args["n_tellers"], args["n_accounts"]) == (1 << 20, 1 << 17)
    assert (args["n_shards"], args["spill_capacity"],
            args["remote_capacity_per_pair"]) == (256, 4096, 73728)
    assert "delivery_backend" not in args  # what `auto` picks on the chip
    assert CONF["n_devices_arg"] == "n_devices" and CONF["chips"] == 4
    assert CONF["remote_capacity_per_pair"] == 9 * (1 << 18) // 4 // 8
    assert CONF["inbox_rows_per_chip"] == 4096 + 4 * 73728 + 8 == 299016
    assert CONF["rows_per_chip"] == (1 << 15) + (1 << 18) == 294912
    assert CONF["reduced"] == [] and set(LIMITS.values()) == {0}
    assert len(CONF["source"]) <= 200 and CONF["source"] != one["source"]
    assert len(CONF["guarantees"]) == 7
    dep = ref.from_config(CONF, 4)
    assert dep.block_rows == CONF["inbox_rows_per_chip"]
    assert dep.rows_a_chip == CONF["rows_per_chip"]
    # the traffic is bank-commands' rule, letter for letter
    rule = load_json(BENCH, "traffic", "bank-commands.json")
    assert TRAFFIC["what"].startswith(rule["what"])
    assert (TRAFFIC["generator"], TRAFFIC["period"], TRAFFIC["levels"]) == (
        rule["generator"], 16, 8)
    assert (TRAFFIC["chunk_steps"], TRAFFIC["warm_chunks"]) == (16, 2)
