"""The bank, cluster-sharded (`build_bank_sharded`) against the plain
reference over C chips (benchmark/reference/bank_sharded.py), step by step at
toy size on the virtual mesh: on 1, 2, 4 and 8 devices under both kernel
families (the ranked exchange and slots family, a CPU's `auto`, and the
sorted exchange and wide slots family, a TPU's), with a spill region a chip
that the toy really uses; every account BY ID equal after every step to the
one-chip `build_bank`'s from the same tellers (location transparency); a
pair capacity that does not hold, counted as the reference counts it; the
mesh's counters through `read_spill`, the drain, a checkpoint and a restore
(onto the same mesh and onto another); and reduce mode left as it was."""

import numpy as np
import pytest

from akka_tpu.models import baseline_benches as bb
from benchmark.harness import BENCH, load_json
from benchmark.reference import bank, bank_sharded as ref

TRAFFIC = load_json(BENCH, "traffic", "bank-commands-mesh.json")
LIMITS = {k: v for k, v in load_json(
    BENCH, "configs", "bank-sharded-128k.json")["limits"].items()
    if k != "compiles_in_window"}  # what the reference judges
# the benchmark's toy: two tellers an account, four slots, 8 logical shards
A, T, S, SHARDS, SPILL, HOST = 256, 512, 4, 8, 64, 8
FAMILIES = [None, "reference"]  # a CPU's `auto` (ranked), a TPU's (wide)
SEED = 2 ** 31 + 39
STEPS = 48


def deployment(chips, pair=None, spill=SPILL):
    return ref.Deployment(A, T, chips, SHARDS, S, spill,
                          pair or (A + T) // chips, HOST)


def build(chips, family, pair=None, spill=SPILL, seed=SEED):
    tellers = ref.seed_tellers(T, A, seed, TRAFFIC)
    system = bb.build_bank_sharded(
        n_tellers=T, n_accounts=A, n_devices=chips, n_shards=SHARDS,
        mailbox_slots=S, spill_capacity=spill, remote_capacity_per_pair=pair,
        tellers=tellers, delivery_backend=family)
    return tellers, system


def reading(system, with_tellers=False):
    """As benchmark/drivers/xbank.py reads the system: by row."""
    from benchmark.drivers import xbank
    return xbank._reading(system, with_tellers)


def teller_index(layout):
    """Row -> teller index (-1 for an account's row)."""
    index = np.full(layout.rows_a_chip * layout.n_devices, -1, np.int64)
    index[layout.teller_row(np.arange(T))] = np.arange(T)
    return index


@pytest.fixture(scope="module")
def one_chip_bank():
    """`build_bank`'s accounts after every step, from the same tellers: what
    location transparency holds the mesh to. `last_teller` as a teller's
    index (-1 before the first command)."""
    tellers = ref.seed_tellers(T, A, SEED, TRAFFIC)
    system = bb.build_bank(n_tellers=T, n_accounts=A, mailbox_slots=S,
                           spill_capacity=SPILL * 8, tellers=tellers)
    steps = []
    for _ in range(STEPS):
        system.run(1)
        acc = {k: system.read_state(k)[:A] for k in bank.ACCOUNT_COLUMNS}
        acc["last_teller"] = np.where(acc["applied"] > 0,
                                      acc["last_teller"] - A, -1)
        steps.append(acc)
    assert system.mailbox_overflow == 0
    return steps


@pytest.mark.parametrize("family", FAMILIES, ids=["auto", "wide"])
@pytest.mark.parametrize("chips", [1, 2, 4, 8])
def test_every_step_is_the_references_and_the_one_chip_banks(
        chips, family, one_chip_bank):
    tellers, system = build(chips, family)
    dep = deployment(chips)
    assert system.m_local == dep.block_rows and system.spill_cap == SPILL
    accounts, queues = bank.zero_accounts(A), ref.empty_queues(dep)
    counters = ref.zero_counters()
    index = teller_index(system.layout)
    for t in range(STEPS):
        system.run(1)
        accounts, queues = ref.step(accounts, queues, t, tellers, TRAFFIC,
                                    dep, counters)
        got = reading(system)
        # the accounts, read where the REFERENCE places them
        assert bank.accounts_wrong(ref.accounts_of(got, dep), accounts) == 0, t
        # every chip's queue row for row, the leftovers packed in front
        have, malformed = ref.queues_of(got, dep)
        assert sum(malformed) == 0
        for chip in range(chips):
            assert bank.queue_wrong(have[chip], 0,
                                    queues[chip]) == 0, (t, chip)
        spill, chunks = ref._regions(got, dep)
        assert spill.tolist() == [r[0] for r in counters["rows"]]
        assert chunks.tolist() == [r[1:] for r in counters["rows"]]
        assert {k: got["counters"][k] for k in ref.COUNTERS} == {
            k: counters[k] for k in ref.COUNTERS}, t
        # location transparency: by id, where build_bank's account stands
        mine = bb.bank_sharded_left_behind(system)["accounts"]
        mine["last_teller"] = np.where(mine["applied"] > 0,
                                       index[mine["last_teller"]], -1)
        for k in bank.ACCOUNT_COLUMNS:
            assert (mine[k] == one_chip_bank[t][k]).all(), (k, t)
    assert counters["spilled"] > 0 and counters["spill_high_water"] > 1
    assert counters["spill_lost"] == counters["exchange_lost"] == 0
    assert system.total_dropped == system.mailbox_overflow == 0
    assert accounts["rejected"].sum() > 0  # order matters here
    assert system.read_spill() == (counters["spilled"],
                                   counters["spill_high_water"])
    if chips > 1:  # a pair carries about 1 / chips of a chip's tells
        assert T // chips ** 2 < counters["exchange_high_water"] < T // chips


@pytest.mark.parametrize("family", FAMILIES, ids=["auto", "wide"])
def test_run_k_and_the_judge_say_correct(family):
    tellers, system = build(4, family, pair=96)
    dep = deployment(4, pair=96)
    got = {}
    for when, k in (("open", 8), ("close", 24), ("after", 8)):
        system.run(k)
        got[when] = reading(system, with_tellers=when == "after")
    numbers = ref.judge(tellers, TRAFFIC, dep, got, LIMITS)
    assert set(numbers) == set(LIMITS) and len(LIMITS) == 10
    assert all(c["value"] == 0 for c in numbers.values()), numbers
    # a teller's row in the payload is the reference's row of it
    told = got["after"]["inbox_payload"][got["after"]["inbox_valid"], 1]
    assert set(told.astype(int)) <= set(dep.teller_row.tolist())


@pytest.mark.parametrize("family", FAMILIES, ids=["auto", "wide"])
def test_pair_capacity_that_does_not_hold_counts_what_it_loses(family):
    """Binomial(128, 1/4) commands a pair a step, 32 +- 5: 30 does not hold.
    The device's count is the reference's, row for row what is left is the
    reference's, and the judge says not correct by the limits named."""
    tellers, system = build(4, family, pair=30)
    dep = deployment(4, pair=30)
    got = {}
    for when, k in (("open", 8), ("close", 8), ("after", 8)):
        system.run(k)
        got[when] = reading(system, with_tellers=when == "after")
    accounts, queues, counters = ref.replay(
        bank.zero_accounts(A), ref.empty_queues(dep), 0, 24, tellers,
        TRAFFIC, dep)
    lost = counters["exchange_lost"]
    assert lost > 0 and system.total_dropped == lost
    assert system.dropped_per_shard.sum() == lost
    assert system.exchange_high_water == counters["exchange_high_water"] > 30
    assert bank.accounts_wrong(ref.accounts_of(got["after"], dep),
                              accounts) == 0
    numbers = ref.judge(tellers, TRAFFIC, dep, got, LIMITS)
    wrong = {k for k, c in numbers.items() if c["value"] > c["limit"]}
    assert wrong == {"messages_dropped", "exchange_dropped"}, numbers
    assert numbers["exchange_dropped"]["value"] == lost
    # every command was applied, or waits, or was counted as lost
    assert got["after"]["state"]["applied"].sum() \
        + got["after"]["inbox_valid"].sum() + lost == 24 * T


@pytest.mark.parametrize("family", FAMILIES, ids=["auto", "wide"])
def test_spill_region_too_small_counts_what_it_loses(family):
    tellers, system = build(4, family, spill=4)
    dep = deployment(4, spill=4)
    system.run(24)
    _, _, counters = ref.replay(bank.zero_accounts(A), ref.empty_queues(dep),
                                0, 24, tellers, TRAFFIC, dep)
    assert system.mailbox_overflow == counters["spill_lost"] > 0
    assert system.read_spill() == (counters["spilled"], 4)  # full, no fuller
    got = bb.bank_sharded_left_behind(system)
    assert got["dropped"] == counters["spill_lost"]
    assert got["exchange_dropped"] == 0


@pytest.mark.parametrize("other_chips", [4, 2])
def test_checkpoint_and_restore_with_mail_in_the_spill_regions(other_chips,
                                                               tmp_path):
    """The spill regions and the mesh's counters ride a checkpoint: restored
    onto the same mesh, 16 more steps leave what the uninterrupted run
    leaves, row for row. Onto another mesh the snapshot is re-sharded: the
    counters are conserved (into shard 0) and no waiting command is lost;
    the ACCOUNTS' rows are this layout's arithmetic on the number of chips,
    so running on from there needs a rebalance (ROADMAP.md B6), not a
    restore."""
    tellers, system = build(4, "reference")
    system.run(21)
    before = system.read_mesh_stats()
    assert before["spilled"] > 0 < before["exchange_high_water"]
    valid = np.asarray(system.inbox_valid).reshape(4, -1)[:, :SPILL]
    assert valid.sum() > 0  # mail waits in the spill regions
    path = system.checkpoint(str(tmp_path))
    _, other = build(other_chips, "reference")
    assert other.restore(path) == 21
    assert other.read_mesh_stats() == before
    assert np.asarray(other.inbox_valid).sum() \
        == np.asarray(system.inbox_valid).sum()
    if other_chips != 4:
        assert np.asarray(other.mesh_stats)[1:].sum() == 0
        return
    system.run(16)
    other.run(16)
    want, have = bb.bank_sharded_left_behind(system), \
        bb.bank_sharded_left_behind(other)
    for k in bank.ACCOUNT_COLUMNS:
        assert (want["accounts"][k] == have["accounts"][k]).all(), k
    assert have["dropped"] == want["dropped"] == 0
    for k in ("inbox_dst", "inbox_type", "inbox_payload", "inbox_valid",
              "spilled", "spill_high_water", "exchange_high_water"):
        assert np.array_equal(want[k], have[k]), k
    dep = deployment(4)
    accounts, _, counters = ref.replay(
        bank.zero_accounts(A), ref.empty_queues(dep), 0, 37, tellers,
        TRAFFIC, dep)
    assert other.read_spill() == (counters["spilled"],
                                  counters["spill_high_water"])
    assert bank.accounts_wrong(ref.accounts_of(reading(other), dep),
                               accounts) == 0


def test_counters_reach_the_drain_the_registry_and_host_stats():
    from akka_tpu.batched.sharded import ShardedBatchedSystem
    from akka_tpu.event.metrics import MetricsRegistry
    tellers = ref.seed_tellers(T, A, 5, TRAFFIC)
    lay = bb.BankShardedLayout(T, A, 4, SHARDS)
    teller = bb.make_bank_teller_sharded(lay)
    system = ShardedBatchedSystem(
        capacity=A + T, behaviors=[bb.bank_account, teller], n_devices=4,
        payload_width=4, host_inbox_per_shard=HOST, mailbox_slots=S,
        spill_capacity=SPILL, remote_capacity_per_pair=96,
        metrics_enabled=True)
    init = {k: np.zeros(A + T, np.int32) for k in bb.TELLER_SPEC}
    for k in init:
        init[k][lay.teller_row(np.arange(T))] = tellers[k]
    rows = np.arange(A + T) % lay.rows_a_chip >= lay.accounts_a_chip
    system.spawn_layout(rows.astype(np.int32), init)
    with pytest.raises(RuntimeError, match="empty system"):
        system.spawn_layout(rows.astype(np.int32), init)
    assert "spilled" not in system.host_stats()  # nothing read yet
    system.run(12)
    step, lanes = system.drain_metrics()
    dep = deployment(4, pair=96)
    _, _, counters = ref.replay(bank.zero_accounts(A), ref.empty_queues(dep),
                                0, 12, tellers, TRAFFIC, dep)
    assert step == 12
    for k in ref.COUNTERS:
        assert lanes[k].tolist() == [counters[k]], k
    registry = MetricsRegistry()
    registry.ingest_device_slab(lanes, step)
    assert registry.gauge("device_spilled").value == counters["spilled"] > 0
    assert registry.gauge("device_exchange_high_water").value \
        == counters["exchange_high_water"]
    stats = system.host_stats()  # as last read: a scrape makes no sync
    assert {k: stats[k] for k in ref.COUNTERS} == {
        k: counters[k] for k in ref.COUNTERS}
    assert stats["dispatches"] == 1


def test_flight_event_names_which_counter_overflowed():
    from akka_tpu.event.flight_recorder import InMemoryFlightRecorder
    for kwargs, named in (({"pair": 30}, {"exchange"}),
                          ({"spill": 4}, {"spill"})):
        _, system = build(4, None, **kwargs)
        system.flight_recorder = recorder = InMemoryFlightRecorder()
        system.run(12)
        system.read_attention()
        events = recorder.of_type("shard_overflow")
        assert events and {w for e in events
                           for w in e["overflowed"]} == named, events


def test_reduce_mode_behind_a_lossless_exchange_carries_none_of_it():
    """`xshard-ring-4chip`'s program: no `mesh_stats`, the step program's
    arguments are the thirteen carry slots and nothing else, and the
    counters read 0."""
    import jax
    from akka_tpu.batched import sharded
    ring = bb.build_cross_shard(8, 64, n_devices=4)
    bb.seed_ring_full(ring)
    assert ring.mesh_stats is None and ring._kept_stats() == ()
    assert sharded.CARRY[-1] == "step_count" and len(sharded.CARRY) == 13
    ring.run(2)
    lowered = ring._step_fn.lower(*ring._carry(), ring.tables, 2)
    n_in = len(jax.tree_util.tree_leaves(ring._carry()))
    assert len(jax.tree_util.tree_leaves(lowered.in_avals)) == n_in
    assert len(jax.tree_util.tree_leaves(lowered.out_info)) == n_in + 2
    assert ring.read_mesh_stats() == dict.fromkeys(ref.COUNTERS, 0)
    assert ring.read_spill() == (0, 0) and ring.exchange_high_water == 0
    # a provisioned pair capacity is something to judge, in reduce mode too
    capped = bb.ShardedBatchedSystem(
        capacity=512, behaviors=[bb.make_crossshard_behavior(128)],
        n_devices=4, host_inbox_per_shard=8, remote_capacity_per_pair=200)
    capped.spawn_block(capped.behaviors[0], 512)
    bb.seed_ring_full(capped)
    capped.run(3)
    assert capped.read_mesh_stats() == {
        "spilled": 0, "spill_high_water": 0, "exchange_high_water": 128}
