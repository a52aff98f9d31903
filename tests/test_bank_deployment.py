"""The bank as a deployment (`build_bank`) against the plain reference
(benchmark/reference/bank.py), step by step at a few hundred actors: both
slots families, through `step()` and through `run(k)`, across a checkpoint
and a restore taken with mail in the spill region, with the two spill
counters equal to the reference's own count of what was carried over; and a
spill region made too small, whose drops are counted and seen."""

import numpy as np
import pytest

from akka_tpu.models import baseline_benches as bb
from benchmark.harness import BENCH, load_json
from benchmark.reference import bank

TRAFFIC = load_json(BENCH, "traffic", "bank-commands.json")
LIMITS = {k: v for k, v in load_json(
    BENCH, "configs", "bank-accounts-128k.json")["limits"].items()
    if k != "compiles_in_window"}  # what the reference judges
# three tellers an account, four slots: arrivals below the cap on average,
# and at every step some mailboxes hold more than four
A, T, S, SPILL = 96, 288, 4, 512
FAMILIES = ["xla", "reference"]  # ranked (a CPU's `auto`), wide (a TPU's)


def build(family, seed=2 ** 31 + 19, spill=SPILL):
    tellers = bank.seed_tellers(T, A, seed, TRAFFIC)
    system = bb.build_bank(n_tellers=T, n_accounts=A, mailbox_slots=S,
                           spill_capacity=spill, tellers=tellers,
                           delivery_backend=family)
    return tellers, system


def assert_equals_reference(system, accounts, queue, t):
    got = bb.bank_left_behind(system, A)
    assert got["steps"] == t
    for k in bank.ACCOUNT_COLUMNS:
        assert (got["accounts"][k] == accounts[k]).all(), (k, t)
    have, malformed = bank.queue_of(got, A)
    assert malformed == 0 and bank.queue_wrong(have, 0, queue) == 0, t
    # what was carried over lies in the spill region, packed at its front
    left = int(got["inbox_valid"][:system.spill_cap].sum())
    assert got["inbox_valid"][:left].all()
    return got, left


@pytest.mark.parametrize("family", FAMILIES)
def test_step_by_step_against_the_reference(family):
    tellers, system = build(family)
    accounts, queue = bank.zero_accounts(A), bank.empty_queue()
    carried = []
    for t in range(48):
        system.step()
        accounts, queue, left = bank.step(accounts, queue, t, tellers,
                                          TRAFFIC, S)
        carried.append(left)
        _, in_spill = assert_equals_reference(system, accounts, queue, t + 1)
        assert in_spill == left
        assert system.read_spill() == (sum(carried), max(carried))
    assert sum(1 for c in carried if c) >= 40  # most steps spill
    assert accounts["rejected"].sum() > 0.1 * accounts["applied"].sum() / 2
    assert system.mailbox_overflow == 0 and system.dropped_messages == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_run_k_leaves_what_the_steps_leave_and_the_judge_says_correct(family):
    tellers, system = build(family)
    got = {}
    for when, k in (("open", 16), ("close", 32), ("after", 16)):
        system.run(k)
        got[when] = bb.bank_left_behind(system, A)
    got["dropped"] = got["after"]["dropped"]
    numbers = bank.judge(tellers, TRAFFIC, A, S, got, LIMITS)
    assert all(c["value"] == 0 for c in numbers.values()), numbers
    accounts, queue, carried = bank.replay(
        bank.zero_accounts(A), bank.empty_queue(), 0, 64, tellers, TRAFFIC, S)
    assert_equals_reference(system, accounts, queue, 64)
    # the counters rode the scan's carry
    assert system.read_spill() == (int(carried.sum()), int(carried.max()))
    assert carried[-1] > 0


@pytest.mark.parametrize("family", FAMILIES)
def test_checkpoint_and_restore_with_mail_in_the_spill_region(family,
                                                              tmp_path):
    tellers, system = build(family)
    system.run(21)
    spilled, high = system.read_spill()
    assert int(np.asarray(system.inbox_valid)[:SPILL].sum()) > 0 < spilled
    path = system.checkpoint(str(tmp_path))
    _, other = build(family)
    assert other.restore(path) == 21
    assert other.read_spill() == (spilled, high)
    for s in (system, other):
        s.run(11)
        s.step()
    accounts, queue, carried = bank.replay(
        bank.zero_accounts(A), bank.empty_queue(), 0, 33, tellers, TRAFFIC, S)
    assert_equals_reference(system, accounts, queue, 33)
    assert_equals_reference(other, accounts, queue, 33)
    assert other.read_spill() == system.read_spill() == (
        int(carried.sum()), int(carried.max()))


def test_counters_reach_the_drain_and_the_registry():
    from akka_tpu.batched import BatchedSystem
    from akka_tpu.event.metrics import MetricsRegistry
    tellers = bank.seed_tellers(T, A, 5, TRAFFIC)
    teller = bb.make_bank_teller(A)
    system = BatchedSystem(capacity=A + T, behaviors=[bb.bank_account, teller],
                           payload_width=4, host_inbox=8, mailbox_slots=S,
                           spill_capacity=SPILL, metrics_enabled=True)
    system.spawn_block(bb.bank_account, A)
    system.spawn_block(teller, T, init_state={
        k: np.asarray(v, np.int32) for k, v in tellers.items()})
    system.run(12)
    step, lanes = system.drain_metrics()
    _, _, carried = bank.replay(bank.zero_accounts(A), bank.empty_queue(), 0,
                                12, tellers, TRAFFIC, S)
    assert step == 12
    assert lanes["spilled"].tolist() == [int(carried.sum())]
    assert lanes["spill_high_water"].tolist() == [int(carried.max())]
    registry = MetricsRegistry()
    registry.ingest_device_slab(lanes, step)
    assert registry.gauge("device_spilled").value == carried.sum() > 0
    assert registry.gauge("device_spill_high_water").value == carried.max()


def test_a_system_with_no_spill_region_carries_zeros():
    ring = bb.build_ring(64, static=False)
    bb.seed_ring_full(ring)
    ring.run(3)
    assert ring.read_spill() == (0, 0)


@pytest.mark.parametrize("family", FAMILIES)
def test_spill_region_too_small_counts_what_it_loses(family):
    tellers, system = build(family, spill=8)
    system.run(24)
    got = bb.bank_left_behind(system, A)
    lost = system.mailbox_overflow
    assert lost > 0 and got["dropped"] == lost
    spilled, high = system.read_spill()
    assert high == 8  # the region was full, and no fuller
    # every command was applied, or waits, or was counted as lost
    assert got["accounts"]["applied"].sum() + got["inbox_valid"].sum() \
        + lost == 24 * T


def test_tellers_rule_is_the_references():
    """`bank_tellers` draws the same columns the reference's seeding does
    (another stream), and the teller behavior tells what `emissions` says."""
    mine = bb.bank_tellers(T, A, seed=3)
    assert set(mine) == set(bb.TELLER_SPEC) == set(bank.TELLER_COLUMNS)
    assert (mine["stride"] % 2 == 1).all() and mine["home"].max() < A
    system = bb.build_bank(n_tellers=T, n_accounts=A, mailbox_slots=S,
                           spill_capacity=SPILL, tellers=mine)
    for t in range(18):
        system.step()
        want = bank.emissions(t, mine, A, TRAFFIC)
        rows = slice(SPILL + A, SPILL + A + T)
        assert (np.asarray(system.inbox_dst)[rows] == want["account"]).all()
        assert (np.asarray(system.inbox_type)[rows] == want["kind"]).all()
        payload = np.asarray(system.inbox_payload)[rows]
        assert (payload[:, 0] == want["amount"]).all()
        assert (payload[:, 1] == want["teller"]).all()
        assert (payload[:, 2:] == 0).all()
