"""The router pool deployment (BASELINE.json config 4, `build_router_pool`)
against its plain reference (benchmark/reference/router.py, numpy only) at
small sizes on the CPU: every routee column, the router's `next` and
`routed`, every producer and the left-over inbox, exactly, step by step and
after T steps. Parametrised over the delivery the chip runs
(`_deliver_merge_wide`, asked for by name, since `auto` on the CPU is scatter)
and the CPU's own `auto`, over both logics, and over pools smaller than,
about as large as and larger than a step's count of tells."""

import re

import numpy as np
import pytest

from akka_tpu.batched import BatchedSystem
from akka_tpu.batched.sharded import ShardedBatchedSystem
from akka_tpu.models.baseline_benches import (PRODUCER_SPEC, ROUTEE_SPEC,
                                              build_fan_in, build_ring,
                                              build_router_pool,
                                              router_pool_left_behind,
                                              router_producers)
from akka_tpu.routing.batched import ROUTER_SPEC, BatchedRouter
from benchmark.harness import BENCH, load_json
from benchmark.reference import router

TRAFFIC = load_json(BENCH, "traffic", "router-random.json")
LIMITS = load_json(BENCH, "configs", "router-pool-100k.json")["limits"]
DELIVERIES = {
    "merge-wide": dict(delivery="merge", delivery_backend="reference"),
    "auto": dict(delivery="auto"),
}
# producers, routees: n divides no step's count / is about a step's count /
# exceeds it (a step then leaves routees without a message)
SIZES = [(2048, 200), (600, 7), (300, 500)]


def build(producers, n_routees, logic="round-robin", **kw):
    return build_router_pool(producers["router"].shape[0], n_routees,
                             logic=logic, producers=producers,
                             mask_period=TRAFFIC["mask_period"], **kw)


def assert_equals(system, producers, n_routees, logic, t, want=None):
    """The system after t steps against `router.after` (or `want`, the
    literal steps' outcome where the test kept one)."""
    n_producers = producers["router"].shape[0]
    assert int(system.step_count) == t
    got = router_pool_left_behind(system)
    want = want or router.after(t, producers, TRAFFIC, n_routees, logic)
    numbers = router.judge(t, producers, TRAFFIC, n_routees, logic, got,
                           LIMITS, want)
    assert {k: c["value"] for k, c in numbers.items()} == dict.fromkeys(
        numbers, 0)
    # and column by column, so that a failure names what differs
    routees, counter, told = want
    for k in router.ROUTEE_COLUMNS:
        np.testing.assert_array_equal(got["routees"][k], routees[k], k)
    assert got["router"] == counter
    # the inbox holds what the producers told last, in the emission slot of
    # each teller's own row, still addressed to the router's ref
    who = router.tells(t - 1, producers, TRAFFIC) if t else np.zeros(
        n_producers, bool)
    slots = np.zeros(got["inbox_valid"].shape[0], bool)
    slots[n_routees + 1:n_routees + 1 + n_producers] = who
    np.testing.assert_array_equal(got["inbox_valid"], slots)
    if t:
        assert (got["inbox_dst"][slots] == n_routees).all()
        np.testing.assert_array_equal(got["inbox_payload"][slots], told)


@pytest.mark.parametrize("logic", router.LOGICS)
@pytest.mark.parametrize("n_producers,n_routees", SIZES)
@pytest.mark.parametrize("delivery", sorted(DELIVERIES))
def test_deployment_equals_the_reference_step_by_step(
        delivery, n_producers, n_routees, logic):
    producers = router.seed_producers(n_producers, n_routees, 2 ** 31 + 5,
                                      TRAFFIC)
    producers["mask"] &= ~(1 << 3)   # at step 3 nobody tells
    producers["mask"] |= 1 << 5      # at step 5 everybody does
    system = build(producers, n_routees, logic, **DELIVERIES[delivery])
    routees, counter = router.zero_state(n_routees)
    inbox = np.zeros((0, 4), np.int64)
    for t in range(1, 9):
        system.step()
        inbox, routees, counter = router.step(
            t - 1, inbox, routees, counter, producers, TRAFFIC, logic)
        assert_equals(system, producers, n_routees, logic, t,
                      (routees, counter, inbox))
        assert inbox.shape[0] == {4: 0, 6: n_producers}.get(
            t, inbox.shape[0])
        if logic == "round-robin":  # the pool's guarantee, after every step
            assert routees["hits"].max() - routees["hits"].min() <= 1
    assert counter["routed"] == routees["hits"].sum() > n_producers


@pytest.mark.parametrize("logic", router.LOGICS)
@pytest.mark.parametrize("t", [1, 16, 37])
@pytest.mark.parametrize("delivery", sorted(DELIVERIES))
def test_deployment_equals_the_reference_after_t_steps(delivery, t, logic):
    producers = router.seed_producers(2048, 200, 2 ** 31 + 6, TRAFFIC)
    system = build(producers, 200, logic, **DELIVERIES[delivery])
    system.run(t)
    assert_equals(system, producers, 200, logic, t)


@pytest.mark.parametrize("logic", router.LOGICS)
def test_chunks_add_up_the_counter_is_in_the_carry(logic):
    producers = router.seed_producers(2048, 200, 7, TRAFFIC)
    chunked, whole = (build(producers, 200, logic,
                            **DELIVERIES["merge-wide"]) for _ in range(2))
    chunked.run(3)
    assert_equals(chunked, producers, 200, logic, 3)
    chunked.run(5)
    whole.run(8)
    assert_equals(chunked, producers, 200, logic, 8)
    for k in list(ROUTEE_SPEC) + list(ROUTER_SPEC):
        np.testing.assert_array_equal(chunked.read_state(k),
                                      whole.read_state(k), k)


def test_checkpoint_restore_continue_equals_an_uninterrupted_run(tmp_path):
    producers = router.seed_producers(2048, 200, 8, TRAFFIC)
    first = build(producers, 200, **DELIVERIES["merge-wide"])
    first.run(6)
    path = first.checkpoint(str(tmp_path))
    second = build(producers, 200, **DELIVERIES["merge-wide"])
    assert second.restore(path) == 6
    assert int(second.read_state("next")[200]) == \
        router.after(6, producers, TRAFFIC, 200)[1]["next"] != 0
    second.run(5)
    assert_equals(second, producers, 200, "round-robin", 11)


def test_a_host_tell_to_the_routers_ref_is_routed_after_the_emissions():
    producers = router.seed_producers(600, 64, 9, TRAFFIC)
    system = build(producers, 64, **DELIVERIES["merge-wide"])
    system.run(4)
    want = router.after(4, producers, TRAFFIC, 64)
    jobs = np.asarray([[1, 2, 3, 7], [1, 0, 5, 6]], np.float32)
    system.tell([64, 64], jobs)  # the ref of the router, as any actor's
    system.step()
    # the host's rows are the inbox's last: ranked after the 4th step's tells
    _, routees, counter = router.step(
        4, np.concatenate([want[2], jobs.astype(np.int64)]), want[0], want[1],
        producers, TRAFFIC, "round-robin")
    for k in router.ROUTEE_COLUMNS:
        np.testing.assert_array_equal(system.read_state(k)[:64], routees[k], k)
    assert int(system.read_state("routed")[64]) == counter["routed"] \
        == want[1]["routed"] + want[2].shape[0] + 2
    first = (want[1]["next"] + want[2].shape[0]) % 64  # the host's first job
    assert routees["last_max"][first] == 7 or routees["last_hits"][first] > 1
    hits = system.read_state("hits")[:64]
    assert hits.max() - hits.min() <= 1 and system.read_state("hits")[64] == 0


def test_int32_totals_wrap_and_agree_modulo_two_to_the_32():
    producers = router.seed_producers(2048, 200, 6, TRAFFIC)
    system = build(producers, 200, **DELIVERIES["merge-wide"])
    start = np.int32(2 ** 31 - 20)  # every total wraps within a step or two
    for k in router.TOTALS:
        system.state[k] = system.state[k].at[:200].set(start)
    system.state["routed"] = system.state["routed"].at[200].set(start)
    system.run(9)
    routees, counter, _ = router.after(9, producers, TRAFFIC, 200)
    wrapped = 0
    for k in router.TOTALS:
        got = system.read_state(k)[:200]
        wrapped += int((got < 0).sum())
        assert ((got.astype(np.int64) - int(start) - routees[k])
                % 2 ** 32 == 0).all(), k
    routed = int(system.read_state("routed")[200])
    assert routed < 0 and (routed - int(start) - counter["routed"]) \
        % 2 ** 32 == 0
    assert wrapped > 0


def test_builder_hands_producers_the_ref_and_no_routee():
    """The deployment as the configuration calls it: dynamic delivery with
    `need_max`, the router a row between routees and producers with its own
    behavior, every producer spawned with that row in its `router` column;
    a producer rewired after spawn tells the row it was given."""
    conf = load_json(BENCH, "configs", "router-pool-100k.json")
    args = dict(conf["builder_args"], n_producers=1024, n_routees=64)
    system = build_router_pool(**args)
    assert isinstance(system, BatchedSystem) and system.topology is None
    assert system.need_max and system.delivery == "auto"
    assert system.capacity == 64 + 1 + 1024
    assert system.inbox_dst.shape[0] == 64 + 1 + 1024 + 8
    (pool,) = system.routers
    assert (pool.row, pool.routee_base, pool.n_routees) == (64, 0, 64)
    assert [b.name for b in system.behaviors] == ["routee", "router",
                                                  "producer"]
    assert (system.read_state("router")[65:] == 64).all()
    assert all(system.state[k].dtype == np.int32 for k in
               list(ROUTEE_SPEC) + list(PRODUCER_SPEC) + list(ROUTER_SPEC))
    # a producer told another ref tells that one: nothing computes a routee
    system.state["router"] = system.state["router"].at[65 + 10].set(3)
    system.state["mask"] = system.state["mask"].at[65 + 10].set(0xFFFF)
    system.run(3)
    routed = int(system.read_state("routed")[64])
    hits = system.read_state("hits")[:64]
    assert hits.sum() == routed + 2  # its two tells went straight to row 3
    a = router_producers(4096, 64, seed=1)
    b = router_producers(4096, 64, seed=2)
    assert set(a) == set(PRODUCER_SPEC) and (a["mask"] != b["mask"]).any()
    assert 0.45 < np.unpackbits(a["mask"].astype(">u2").view(np.uint8)
                                ).mean() < 0.55


def test_routed_rides_the_metrics_drain_beside_the_delivery_counts():
    """With the slab on, a drain carries each pool's `routed` beside the
    histograms, and the registry shows it as a gauge; `read_routers` reads
    the router's row whatever the slab."""
    from akka_tpu.event.metrics import MetricsRegistry
    from akka_tpu.models.baseline_benches import (make_pool_producer,
                                                  pool_routee)
    pool = BatchedRouter("round-robin", row=8, routee_base=0, n_routees=8)
    producer = make_pool_producer()
    system = BatchedSystem(8 + 1 + 64, [pool_routee, pool.behavior, producer],
                           host_inbox=8, need_max=True, routers=[pool],
                           metrics_enabled=True)
    system.spawn_block(pool_routee, 8)
    system.spawn_block(pool.behavior, 1)
    system.spawn_block(producer, 64,
                       init_state=router_producers(64, pool.row, seed=3))
    assert system.read_routers() == [{"row": 8, "next": 0, "routed": 0}]
    system.run(4)
    step, lanes = system.drain_metrics()
    (counters,) = system.read_routers()
    assert step == 4 and counters["routed"] > 64
    assert counters["next"] == counters["routed"] % 8
    assert lanes["routed"].tolist() == [counters["routed"]]
    assert lanes["mailbox_occupancy"].sum() > 0
    reg = MetricsRegistry()
    reg.ingest_device_slab(lanes, step)
    assert f"akka_device_routed {counters['routed']}" in reg.expose()
    assert reg.device_histogram("routed") is None
    assert reg.device_histogram("mailbox_occupancy").count > 0
    assert build_ring(8, static=False).read_routers() == []


def test_pool_description_refuses_what_it_cannot_route():
    with pytest.raises(ValueError, match="unknown routing logic"):
        BatchedRouter("smallest-mailbox", 8, 0, 8)
    with pytest.raises(ValueError, match="unknown routing logic"):
        build_router_pool(64, 8, logic="consistent-hash")
    with pytest.raises(ValueError, match="n_routees"):
        BatchedRouter("random", 8, 0, 0)
    with pytest.raises(ValueError, match="among its own routees"):
        BatchedRouter("round-robin", 3, 0, 8)
    pool = BatchedRouter("round-robin", 8, 0, 8)
    with pytest.raises(ValueError, match="not among the system's"):
        BatchedSystem(16, [build_ring(8, static=False).behaviors[0]],
                      routers=[pool])
    with pytest.raises(ValueError, match="does not fit"):
        BatchedSystem(8, [pool.behavior], routers=[pool])
    from akka_tpu.ops.segment import StaticTopology
    topo = StaticTopology.from_dst_table(((np.arange(16) + 1) % 16)[:, None])
    with pytest.raises(ValueError, match="StaticTopology"):
        BatchedSystem(16, [pool.behavior], routers=[pool], topology=topo)


def test_sharded_runtime_refuses_a_router():
    pool = BatchedRouter("round-robin", 8, 0, 8)
    with pytest.raises(NotImplementedError, match="ISSUE 32"):
        ShardedBatchedSystem(16, [pool.behavior], n_devices=2,
                             routers=[pool])


@pytest.mark.parametrize("which", ["ring", "fan-in"])
def test_a_system_with_no_router_lowers_no_route_stage(which):
    """The stage is a Python-level branch: the cells that were there keep
    their step programs."""
    system = build_ring(512, static=False) if which == "ring" \
        else build_fan_in(512, 8, static=False)
    assert system.routers == () and system._core.routers == ()
    text = system._run_jit.lower(*system._carry(), 2,
                                 system._topo_arrays).as_text(debug_info=True)
    assert "akka.deliver" in text and "akka.route" not in text
    pool = build_router_pool(512, 8)
    text = pool._run_jit.lower(*pool._carry(), 2,
                               pool._topo_arrays).as_text(debug_info=True)
    assert set(re.findall(r"akka\.route\.(\w+)", text)) == {"rank",
                                                            "readdress"}
