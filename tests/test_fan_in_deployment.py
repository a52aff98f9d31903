"""The fan-in aggregator deployment (BASELINE.json config 3,
`build_fan_in`) against its plain reference (benchmark/reference/fanin.py,
numpy only) at a small size on the CPU: every collector column, every leaf
and the left-over inbox, exactly. Parametrised over the delivery the chip
runs (`_deliver_merge_wide` with `need_max`, asked for by name, since `auto`
on the CPU is scatter and would never touch the kernel the cell times), the
CPU's own `auto`, and the wiring compiled into a StaticTopology."""

import numpy as np
import pytest

from akka_tpu.batched import BatchedSystem
from akka_tpu.models.baseline_benches import (COLLECTOR_SPEC, LEAF_SPEC,
                                              build_fan_in, fan_in_leaves)
from benchmark.harness import BENCH, load_json
from benchmark.reference import fanin

TRAFFIC = load_json(BENCH, "traffic", "fanin-tick.json")
LIMITS = load_json(BENCH, "configs", "fanin-aggregator-1m.json")["limits"]
N_LEAVES, N_COLL = 2048, 16
DELIVERIES = {
    "merge-wide": dict(static=False, delivery="merge",
                       delivery_backend="reference"),
    "auto": dict(static=False, delivery="auto"),
    "static": dict(static=True),
}


def build(leaves, n_coll=N_COLL, **kw):
    return build_fan_in(leaves["collector"].shape[0], n_coll, leaves=leaves,
                        reading_levels=TRAFFIC["reading_levels"],
                        alarm_period=TRAFFIC["alarm_period"], **kw)


def left_behind(system, n_coll, n_leaves) -> dict:
    rows = slice(n_coll, n_coll + n_leaves)
    return {"collectors": {k: system.read_state(k)[:n_coll]
                           for k in fanin.COLLECTOR_COLUMNS},
            "leaves": {k: system.read_state(k)[rows]
                       for k in fanin.LEAF_COLUMNS},
            "inbox_dst": np.asarray(system.inbox_dst),
            "inbox_payload": np.asarray(system.inbox_payload),
            "inbox_valid": np.asarray(system.inbox_valid),
            "dropped": system.dropped_messages + system.mailbox_overflow}


def assert_equals_reference(system, leaves, n_coll, t):
    n_leaves = leaves["collector"].shape[0]
    assert int(system.step_count) == t
    got = left_behind(system, n_coll, n_leaves)
    numbers = fanin.judge(t, leaves, TRAFFIC, n_coll, got, LIMITS)
    assert {k: c["value"] for k, c in numbers.items()} == dict.fromkeys(
        numbers, 0)
    # and column by column, so that a failure names what differs
    want, told = fanin.after(t, leaves, TRAFFIC, n_coll)
    for k in fanin.COLLECTOR_COLUMNS:
        np.testing.assert_array_equal(got["collectors"][k], want[k], k)
    for k in fanin.LEAF_COLUMNS:
        np.testing.assert_array_equal(got["leaves"][k], leaves[k], k)
    # the inbox holds what the leaves told last, one message a leaf, in the
    # emission slot of the leaf's own row
    slots = slice(n_coll, n_coll + n_leaves)
    assert got["inbox_valid"].sum() == (n_leaves if t else 0)
    if t:
        assert got["inbox_valid"][slots].all()
        np.testing.assert_array_equal(got["inbox_dst"][slots],
                                      leaves["collector"])
        np.testing.assert_array_equal(got["inbox_payload"][slots], told)


@pytest.mark.parametrize("t", [1, 15, 16, 37])
@pytest.mark.parametrize("delivery", sorted(DELIVERIES))
def test_deployment_equals_the_reference(delivery, t):
    leaves = fanin.seed_leaves(N_LEAVES, N_COLL, 2 ** 31 + 5, TRAFFIC)
    system = build(leaves, **DELIVERIES[delivery])
    system.run(t)
    assert_equals_reference(system, leaves, N_COLL, t)


@pytest.mark.parametrize("delivery", sorted(DELIVERIES))
def test_chunks_and_single_steps_agree(delivery):
    leaves = fanin.seed_leaves(N_LEAVES, N_COLL, 7, TRAFFIC)
    system = build(leaves, **DELIVERIES[delivery])
    system.run(16)
    system.step()
    system.run(3)
    assert_equals_reference(system, leaves, N_COLL, 20)


@pytest.mark.parametrize("delivery", sorted(DELIVERIES))
def test_collector_nobody_tells_stays_at_zero(delivery):
    leaves = fanin.seed_leaves(N_LEAVES, N_COLL, 3, TRAFFIC)
    leaves["collector"][leaves["collector"] == 5] = 6  # in-degree 0
    system = build(leaves, **DELIVERIES[delivery])
    system.run(19)
    assert_equals_reference(system, leaves, N_COLL, 19)
    for k in fanin.COLLECTOR_COLUMNS:
        assert system.read_state(k)[5] == 0, k


@pytest.mark.parametrize("delivery", sorted(DELIVERIES))
def test_all_leaves_on_one_collector(delivery):
    leaves = fanin.seed_leaves(N_LEAVES, N_COLL, 4, TRAFFIC)
    leaves["collector"][:] = 9
    system = build(leaves, **DELIVERIES[delivery])
    system.run(18)
    assert_equals_reference(system, leaves, N_COLL, 18)
    assert system.read_state("msgs")[9] == 17 * N_LEAVES


@pytest.mark.parametrize("delivery", sorted(DELIVERIES))
def test_int32_totals_wrap_and_agree_modulo_two_to_the_32(delivery):
    leaves = fanin.seed_leaves(N_LEAVES, N_COLL, 6, TRAFFIC)
    system = build(leaves, **DELIVERIES[delivery])
    start = np.int32(2 ** 31 - 100)  # every total wraps within a step or two
    for k in fanin.TOTALS:
        system.state[k] = system.state[k].at[:N_COLL].set(start)
    system.run(9)
    want, _ = fanin.after(9, leaves, TRAFFIC, N_COLL)
    wrapped = 0
    for k in fanin.TOTALS:
        got = system.read_state(k)[:N_COLL]
        wrapped += int((got < 0).sum())
        assert ((got.astype(np.int64) - int(start) - want[k])
                % 2 ** 32 == 0).all(), k
    assert wrapped > 0


def test_builder_takes_refs_from_state_not_from_actor_ids():
    """The deployment as the configuration calls it: no StaticTopology, the
    destination out of the `collector` column set at spawn, `need_max`,
    integer totals, no capacity padded for a modulo trick."""
    conf = load_json(BENCH, "configs", "fanin-aggregator-1m.json")
    args = dict(conf["builder_args"], n_leaves=N_LEAVES, n_collectors=N_COLL)
    system = build_fan_in(**args)
    assert isinstance(system, BatchedSystem) and system.topology is None
    assert system.need_max and system.delivery == "auto"
    assert system.capacity == N_LEAVES + N_COLL
    assert (system.payload_width, system.out_degree) == (4, 1)
    assert all(system.state[k].dtype == np.int32
               for k in list(COLLECTOR_SPEC) + list(LEAF_SPEC))
    # rewire one leaf after spawn: its tells follow the column
    wired = system.read_state("collector")
    leaf = N_COLL + 100
    other = (wired[leaf] + 1) % N_COLL
    system.state["collector"] = system.state["collector"].at[leaf].set(other)
    before = np.bincount(wired[N_COLL:], minlength=N_COLL)
    system.run(2)
    msgs = system.read_state("msgs")[:N_COLL]
    assert msgs[other] == before[other] + 1
    assert msgs[wired[leaf]] == before[wired[leaf]] - 1


def test_default_leaves_come_from_the_seed():
    a = fan_in_leaves(4096, 16, seed=1)
    b = fan_in_leaves(4096, 16, seed=2)
    assert set(a) == set(LEAF_SPEC)
    assert (a["collector"] != b["collector"]).any()
    assert a["collector"].min() >= 0 and a["collector"].max() < 16
    assert 0 < (a["alarm_level"] > 0).mean() < 1 / 32
    s = build_fan_in(4096, 16, static=False, seed=1)
    np.testing.assert_array_equal(s.read_state("collector")[16:],
                                  a["collector"])
