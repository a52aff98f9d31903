"""The five BASELINE bench configs run and count correctly at test scale
(akka-bench-jmh parity surface, SURVEY.md §6)."""

import numpy as np

from akka_tpu.models.baseline_benches import (build_cross_shard, build_fan_in,
                                              build_ping_pong, build_ring,
                                              build_router_pool, seed_ring_full)


def test_ring_static_and_dynamic_agree():
    for static in (True, False):
        s = build_ring(512, static=static)
        seed_ring_full(s)
        s.run(6)
        s.block_until_ready()
        assert (s.read_state("received") == 6).all(), f"static={static}"


def test_fan_in_counts():
    s = build_fan_in(n_leaves=2000, n_collectors=1000)
    s.run(4)
    s.block_until_ready()
    msgs = s.read_state("msgs")[:1000]
    # always_on leaves emit steps 1..4; deliveries land steps 2..4 (+1 lag)
    assert msgs.sum() == 3 * 2000


def test_router_round_robin_spread():
    n_routees, n_producers = 64, 1024
    s = build_router_pool(n_producers=n_producers, n_routees=n_routees)
    s.run(5)
    s.block_until_ready()
    hits = s.read_state("hits")[:n_routees]
    # producers tell the ROUTER's ref on about half the steps each; what was
    # told at steps 0..3 has been routed, none of it stayed with the router
    routed = int(s.read_state("routed")[n_routees])
    assert hits.sum() == routed and s.read_state("hits")[n_routees] == 0
    assert 0.4 * 4 * n_producers < routed < 0.6 * 4 * n_producers
    # one counter a pool: the routees' loads differ by at most one
    assert hits.max() - hits.min() <= 1


def test_cross_shard_ring_delivers():
    s = build_cross_shard(n_shards=8, entities_per_shard=32)
    seed_ring_full(s)
    s.run(5)
    s.block_until_ready()
    assert (s.read_state("received") == 5).all()
    assert s.total_dropped == 0


def test_ping_pong_round_trip():
    s = build_ping_pong()
    s.tell(0, [1.0, 0, 0, 0])
    s.run(10)
    s.block_until_ready()
    hits = s.read_state("hits")
    assert hits[0] + hits[1] == 10
