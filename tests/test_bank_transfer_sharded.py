"""Savina's Bank Transaction cluster-sharded (`build_bank_transfer_sharded`):
accounts that answer every message and stash, placed by id over a mesh,
against the plain reference over C chips
(benchmark/reference/bank_transfer_sharded.py) after every step, at toy size
on the CPU's virtual devices: on 1, 2 and 4 chips under both kernel families
(the ranked exchange and slots family, a CPU's `auto`, and the sorted
exchange and wide slots family, a TPU's); every capacity too small, each
counting exactly what the reference says it loses; the emission counters a
shard through the readers, the drain, the registry, the flight event and a
checkpoint; what the mesh still refuses. Each case builds and compiles one
system."""

import numpy as np
import pytest

from akka_tpu.batched.core import EMIT_STATS
from akka_tpu.models import baseline_benches as bb
from benchmark.reference import bank_transfer_sharded as ref

# 512 accounts in 8 logical shards and 512 tellers: ~280 tells a step, 3 in
# 4 across chips, stashes of 4 and more within a few steps, and steps at
# which the budget leaves mail retained
A, SHARDS, S, K, C, SPILL, HOST = 512, 8, 16, 4, 64, 256, 8
FAMILIES = [None, "reference"]  # a CPU's `auto` (ranked), a TPU's (wide)
SEED = 2 ** 31 + 45
STEPS = 16


def build(chips, family=None, pair=None, spill=SPILL, stash=C, seed=SEED,
          **kw):
    keys = ref.seed_keys(A, seed)
    system = bb.build_bank_transfer_sharded(
        n_accounts=A, n_tellers=A, n_shards=SHARDS, n_devices=chips,
        mailbox_slots=S, emit_width=K, stash_capacity=stash,
        spill_capacity=spill, remote_capacity_per_pair=pair, keys=keys,
        delivery_backend=family, **kw)
    dep = ref.Deployment(A, A, chips, SHARDS, S, K, stash, spill,
                         system.pair_cap, HOST)
    return keys, dep, system


def reading(system):
    return bb.transfer_sharded_left_behind(system)


def every_step(system, keys, dep, steps=STEPS):
    """Steps the system and the reference side by side; returns the last
    reading and the reference's counters (cumulative from the seed)."""
    world, counters = ref.World.start(keys, dep), ref.zero_counters()
    for _ in range(steps):
        system.run(1)
        ref.step(world, dep, counters)
        got = reading(system)
        assert got["steps"] == world.t
        assert ref.compare(got, world, dep, keys) == dict.fromkeys(
            ("accounts_wrong", "tellers_wrong", "stashes_wrong",
             "queue_wrong"), 0), world.t
        assert ref.counters_wrong(got["counters"], counters) == {
            "stashes_wrong": 0, "queue_wrong": 0}, world.t
    return got, counters


@pytest.mark.parametrize("family", FAMILIES, ids=["auto", "wide"])
@pytest.mark.parametrize("chips", [1, 2, 4])
def test_every_step_is_the_references(chips, family):
    keys, dep, system = build(chips, family)
    assert system.emit_rows == A // chips
    assert system.inbox_dst.shape[0] // chips \
        == SPILL + chips * system.pair_cap + HOST
    got, counters = every_step(system, keys, dep)
    # the path did its work: stashes, retention, a full budget, a chain
    # run to its teller, the exchange carrying the most of it
    assert counters["stashed"] > 0 and counters["retained_by_budget"] > 0
    assert counters["stash_high_water"] >= 4
    assert counters["emit_high_water"] == K
    assert int(got["tellers"]["done"].sum()) > A
    assert ref.ledger(got, dep) == 0 and ref.unaccounted(got, dep) == 0
    assert got["dropped"] == 0 and counters["stash_overflow"] == 0


@pytest.mark.parametrize("family", FAMILIES, ids=["auto", "wide"])
def test_a_pair_too_small_counts_what_it_loses(family):
    keys, dep, system = build(4, family, pair=30)
    got, counters = every_step(system, keys, dep, 8)
    assert got["counters"]["exchange_dropped"] == counters[
        "exchange_dropped"] > 0
    assert got["counters"]["exchange_high_water"] > 30
    assert ref.unaccounted(got, dep) == 0


def test_a_spill_region_too_small_counts_in_mailbox_overflow():
    keys, dep, system = build(4, spill=1)
    got, counters = every_step(system, keys, dep, 8)
    assert system.mailbox_overflow == counters["mail_dropped"] > 0
    assert got["counters"]["spill_high_water"] == 1


def test_a_full_stash_counts_and_the_flight_event_names_it():
    from akka_tpu.event.flight_recorder import InMemoryFlightRecorder
    keys, dep, system = build(4, stash=2)
    system.flight_recorder = recorder = InMemoryFlightRecorder()
    got, counters = every_step(system, keys, dep, 6)
    lost = system.read_emit_stats()["stash_overflow"]
    assert lost == counters["stash_overflow"] > 0
    assert ref.unaccounted(got, dep) == 0
    events = recorder.of_type("shard_overflow")
    assert events and all(e["overflowed"] == ("stash",) for e in events)
    assert {e["shard"] for e in events} <= set(range(4))
    n = len(events)
    system.read_emit_stats()  # no growth since: no new event
    assert len(recorder.of_type("shard_overflow")) == n


def test_counters_reach_the_drain_the_registry_and_host_stats():
    from akka_tpu.event.metrics import MetricsRegistry
    keys, dep, system = build(4, metrics_enabled=True)
    assert "stashed" not in system.host_stats()  # nothing read yet
    system.run(10)
    world, counters = ref.World.start(keys, dep), ref.zero_counters()
    for _ in range(10):
        ref.step(world, dep, counters)
    step, lanes = system.drain_metrics()
    assert step == 10
    names = EMIT_STATS + ("spilled", "spill_high_water",
                          "exchange_high_water")  # the device's lanes
    for k in names:
        assert lanes[k].tolist() == [counters[k]], k
    registry = MetricsRegistry()
    registry.ingest_device_slab(lanes, step)
    assert registry.gauge("device_stashed").value == counters["stashed"] > 0
    registry.register_collector("device_host", system.host_stats)
    collected = registry.snapshot()["collected"]
    for k in names:  # as last read: a scrape makes no sync
        assert collected[f"device_host_{k}"] == counters[k], k
    assert system.read_emit_stats() == {k: counters[k] for k in EMIT_STATS}


def test_checkpoint_and_restore_with_stashes_retention_and_counters(
        tmp_path):
    keys, dep, system = build(4)
    system.run(9)
    before = reading(system)
    # something in flight of every kind the checkpoint has to keep
    assert before["stash"]["stash_size"].sum() > 0
    assert before["counters"]["stashed"] > 0
    assert np.asarray(system.inbox_valid).sum() > 0
    path = system.checkpoint(str(tmp_path))
    _, _, other = build(4)
    other.restore(path)
    again = reading(other)
    assert again["counters"] == before["counters"]
    assert ref.compare(again, ref.world_of(before, dep, keys), dep,
                       keys) == dict.fromkeys(
        ("accounts_wrong", "tellers_wrong", "stashes_wrong",
         "queue_wrong"), 0)
    system.run(5)
    other.run(5)
    assert reading(other)["counters"] == reading(system)["counters"]
    assert ref.compare(reading(other), ref.world_of(reading(system), dep,
                                                    keys), dep,
                       keys)["accounts_wrong"] == 0
