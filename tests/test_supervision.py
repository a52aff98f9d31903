"""In-graph vectorized supervision (ISSUE 2): directive semantics, restart
accounting (retry windows, exponential backoff, exhaustion -> STOP),
dead-letter pricing for mail to down lanes, chaos-seed parity across
delivery backends, sharded counter parity, and the host restart_rows
generation-bump regression.

Every assertion here is EXACT (==, array_equal): the chaos schedule is a
pure function of (seed, step, lane) replayable by an un-jitted numpy
oracle, and the supervision pass is deterministic masked arithmetic — any
drift between the jitted run and the oracle is a bug, not noise.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from akka_tpu.actor.supervision import Directive
from akka_tpu.batched import Emit, LaneSupervisor, behavior
from akka_tpu.batched.core import BatchedSystem
from akka_tpu.batched.sharded import ShardedBatchedSystem
from akka_tpu.event.flight_recorder import InMemoryFlightRecorder
from akka_tpu.testkit import chaos

P = 4  # payload width used throughout


def make_acc(supervisor, name="acc", guard=False):
    """always_on accumulator: one increment per live step — the unit of
    'work done' every oracle below recomputes."""

    @behavior(name, {"acc": ((), jnp.float32)}, always_on=True,
              supervisor=supervisor, nonfinite_guard=guard)
    def acc(state, inbox, ctx):
        return {"acc": state["acc"] + 1.0}, Emit.none(1, P)

    return acc


def make_failing(fail_steps, supervisor, name="failing"):
    """always_on accumulator that deterministically fails on the given
    step numbers (the scripted-fault twin of chaos.inject)."""
    fail_arr = jnp.asarray(sorted(fail_steps), jnp.int32)

    @behavior(name, {"acc": ((), jnp.float32), "_failed": ((), jnp.bool_)},
              always_on=True, supervisor=supervisor)
    def failing(state, inbox, ctx):
        hit = jnp.any(fail_arr == ctx.step)
        return ({"acc": state["acc"] + 1.0,
                 "_failed": state["_failed"] | hit}, Emit.none(1, P))

    return failing


def crash_oracle(seed, rate, n, steps):
    """Replay the chaos schedule: per-(step, lane) hit grid."""
    lanes = np.arange(n)
    return np.stack([chaos.chaos_hit_np(seed, s, lanes, rate,
                                        chaos.CRASH_SALT)
                     for s in range(steps)])  # [steps, n]


# --------------------------------------------------------------- directives
def test_resume_keeps_state_and_clears_flag():
    seed, rate, n, steps = 3, 0.1, 64, 40
    b = chaos.inject(make_acc(LaneSupervisor(directive=Directive.RESUME)),
                     seed=seed, crash_rate=rate)
    sys = BatchedSystem(n, [b], payload_width=P)
    sys.spawn_block(0, n)
    sys.run(steps)

    hits = crash_oracle(seed, rate, n, steps)
    # a hit step's update is discarded (poisoned receive), state kept
    np.testing.assert_array_equal(
        sys.read_state("acc"), (steps - hits.sum(0)).astype(np.float32))
    c = sys.supervision_counts
    assert c["failed"] == int(hits.sum()) > 0
    assert c["resumed"] == c["failed"]
    assert c["restarted"] == c["stopped"] == c["escalated"] == 0
    # resume is NOT a new incarnation
    np.testing.assert_array_equal(sys.read_state("_gen"), np.zeros(n))
    assert not sys.any_failed()


def test_restart_resets_state_and_bumps_gen():
    seed, rate, n, steps = 42, 0.05, 64, 50
    b = chaos.inject(make_acc(LaneSupervisor(directive=Directive.RESTART)),
                     seed=seed, crash_rate=rate)
    sys = BatchedSystem(n, [b], payload_width=P)
    sys.spawn_block(0, n)
    sys.run(steps)

    hits = crash_oracle(seed, rate, n, steps)
    o_acc = np.zeros(n)
    for s in range(steps):  # immediate restart: reset in the failing pass
        o_acc = np.where(hits[s], 0.0, o_acc + 1.0)
    np.testing.assert_array_equal(sys.read_state("acc"),
                                  o_acc.astype(np.float32))
    np.testing.assert_array_equal(sys.read_state("_gen"), hits.sum(0))
    c = sys.supervision_counts
    assert c["failed"] == c["restarted"] == int(hits.sum()) > 0
    assert not sys.any_failed()


def test_restart_state_override():
    seed, rate, n, steps = 9, 0.08, 32, 30
    sup = LaneSupervisor(directive=Directive.RESTART,
                         restart_state={"acc": 7.0})
    b = chaos.inject(make_acc(sup), seed=seed, crash_rate=rate)
    sys = BatchedSystem(n, [b], payload_width=P)
    sys.spawn_block(0, n)
    sys.run(steps)

    hits = crash_oracle(seed, rate, n, steps)
    o_acc = np.zeros(n)
    for s in range(steps):
        o_acc = np.where(hits[s], 7.0, o_acc + 1.0)
    assert hits.sum() > 0
    np.testing.assert_array_equal(sys.read_state("acc"),
                                  o_acc.astype(np.float32))


def test_stop_kills_lane_in_graph():
    seed, rate, n, steps = 5, 0.05, 64, 40
    b = chaos.inject(make_acc(LaneSupervisor(directive=Directive.STOP)),
                     seed=seed, crash_rate=rate)
    sys = BatchedSystem(n, [b], payload_width=P)
    sys.spawn_block(0, n)
    sys.run(steps)

    hits = crash_oracle(seed, rate, n, steps)
    ever = hits.any(0)
    # first hit kills the lane: acc froze at the first-hit step count
    first = np.where(ever, hits.argmax(0), steps)
    np.testing.assert_array_equal(sys.read_state("acc"),
                                  first.astype(np.float32))
    alive = np.asarray(jax.device_get(sys.alive))
    np.testing.assert_array_equal(alive, ~ever)
    c = sys.supervision_counts
    assert c["failed"] == c["stopped"] == int(ever.sum()) > 0
    assert c["restarted"] == 0
    assert not sys.any_failed()  # dead rows do not re-report


def test_escalate_suspends_until_host_resolves():
    sup = LaneSupervisor(directive=Directive.ESCALATE)
    b = make_failing([1], sup)
    sys = BatchedSystem(4, [b], payload_width=P)
    sys.spawn_block(0, 4)
    sys.run(5)

    c = sys.supervision_counts
    assert c["failed"] == 4 and c["escalated"] == 4
    assert sys.any_escalated()
    np.testing.assert_array_equal(sys.escalated_rows(), np.arange(4))
    # suspended since the failure: only step 0's update landed
    np.testing.assert_array_equal(sys.read_state("acc"), np.full(4, 1.0))
    assert sys.any_failed()  # escalation does NOT clear the error lane

    # host resolution: clear_failed lowers both flags, the lanes resume
    sys.clear_failed(sys.escalated_rows())
    assert not sys.any_escalated()
    sys.run(3)
    # steps 5..7 land (fail_step 1 is in the past), +3 increments
    np.testing.assert_array_equal(sys.read_state("acc"), np.full(4, 4.0))


# ------------------------------------------------- restart accounting
def test_backoff_delays_restart():
    sup = LaneSupervisor(min_backoff_steps=4, max_backoff_steps=16)
    sys = BatchedSystem(2, [make_failing([2], sup)], payload_width=P)
    sys.spawn_block(0, 2)
    sys.run(12)

    # fail@2 (update discarded, acc=2) -> backoff 4<<0=4 -> restart due
    # at step 6 -> suspended 3..6 -> acc counts steps 7..11 = 5
    np.testing.assert_array_equal(sys.read_state("acc"), np.full(2, 5.0))
    np.testing.assert_array_equal(sys.read_state("_retries"), np.full(2, 1))
    np.testing.assert_array_equal(sys.read_state("_gen"), np.full(2, 1))
    np.testing.assert_array_equal(sys.read_state("_restart_at"),
                                  np.full(2, -1))
    c = sys.supervision_counts
    assert c["failed"] == 2 and c["restarted"] == 2
    assert not sys.any_failed()


def test_backoff_doubles_and_caps():
    # fail every live step: restart delays walk 2, 4, 8, 8 (cap)
    sup = LaneSupervisor(min_backoff_steps=2, max_backoff_steps=8)

    @behavior("alwaysfail", {"_failed": ((), jnp.bool_)}, always_on=True,
              supervisor=sup)
    def alwaysfail(state, inbox, ctx):
        return {"_failed": jnp.asarray(True)}, Emit.none(1, P)

    sys = BatchedSystem(1, [alwaysfail], payload_width=P)
    sys.spawn_block(0, 1)
    # fail@0 -> due@2; fail@3 -> due@7; fail@8 -> due@16; fail@17 -> due@25
    sys.run(18)
    assert int(sys.read_state("_retries")[0]) == 4
    np.testing.assert_array_equal(sys.read_state("_restart_at"), [25])
    c = sys.supervision_counts
    assert c["failed"] == 4 and c["restarted"] == 3  # 4th still backing off


def test_window_expiry_resets_retry_budget():
    # one retry per 10-step window: failures at 2 and 20 BOTH restart
    # because the second failure opens a fresh window
    sup = LaneSupervisor(max_nr_of_retries=1, within_steps=10)
    sys = BatchedSystem(2, [make_failing([2, 20], sup)], payload_width=P)
    sys.spawn_block(0, 2)
    sys.run(24)

    c = sys.supervision_counts
    assert c["failed"] == 4 and c["restarted"] == 4 and c["stopped"] == 0
    np.testing.assert_array_equal(sys.read_state("_gen"), np.full(2, 2))
    np.testing.assert_array_equal(sys.read_state("_window_start"),
                                  np.full(2, 20))
    np.testing.assert_array_equal(sys.read_state("_retries"), np.full(2, 1))
    # resets at 2 and 20 -> acc counts steps 21..23
    np.testing.assert_array_equal(sys.read_state("acc"), np.full(2, 3.0))


def test_max_retries_exhausted_stops():
    # same failure schedule, UNBOUNDED window: the second failure finds the
    # retry budget spent and degrades to STOP (OneForOneStrategy parity)
    sup = LaneSupervisor(max_nr_of_retries=1, within_steps=0)
    sys = BatchedSystem(2, [make_failing([2, 20], sup)], payload_width=P)
    sys.spawn_block(0, 2)
    sys.run(24)

    c = sys.supervision_counts
    assert c["failed"] == 4 and c["restarted"] == 2 and c["stopped"] == 2
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(sys.alive)), np.zeros(2, np.bool_))
    # restart@2 reset acc; updates landed steps 3..19 then died at 20
    np.testing.assert_array_equal(sys.read_state("acc"), np.full(2, 17.0))
    np.testing.assert_array_equal(sys.read_state("_gen"), np.full(2, 2))


def test_zero_retries_means_never_restart():
    sup = LaneSupervisor(max_nr_of_retries=0)
    sys = BatchedSystem(1, [make_failing([1], sup)], payload_width=P)
    sys.spawn_block(0, 1)
    sys.run(4)
    c = sys.supervision_counts
    assert c["failed"] == 1 and c["stopped"] == 1 and c["restarted"] == 0


# ---------------------------------------------------------- dead letters
def test_mail_to_backoff_lane_dead_letters():
    sup = LaneSupervisor(min_backoff_steps=4, max_backoff_steps=16)
    target = make_failing([2], sup, name="target")

    @behavior("pinger", {}, always_on=True)
    def pinger(state, inbox, ctx):
        return {}, Emit.single(0, jnp.zeros((P,)), 1, P)

    sys = BatchedSystem(2, [target, pinger], payload_width=P)
    sys.spawn_block(0, 1)   # target = row 0
    sys.spawn_block(1, 1)   # pinger = row 1
    sys.run(12)

    # pinger's emission from step s arrives at step s+1: target receives
    # from step 1 on. Down (old_failed at step start) for steps 3..6 ->
    # exactly 4 dead letters; step 2's message was consumed by the receive
    # whose update the failure discarded (not a dead letter).
    c = sys.supervision_counts
    assert c["dead_letters"] == 4
    assert c["failed"] == 1 and c["restarted"] == 1


def test_mail_to_device_stopped_lane_dead_letters():
    sup = LaneSupervisor(directive=Directive.STOP)
    target = make_failing([2], sup, name="target")

    @behavior("pinger", {}, always_on=True)
    def pinger(state, inbox, ctx):
        return {}, Emit.single(0, jnp.zeros((P,)), 1, P)

    sys = BatchedSystem(2, [target, pinger], payload_width=P)
    sys.spawn_block(0, 1)
    sys.spawn_block(1, 1)
    sys.run(10)
    # stopped in step 2's pass -> every arrival from step 3 on (7 steps)
    # is addressed to a dead supervised lane
    assert sys.supervision_counts["dead_letters"] == 7


# ------------------------------------------------------ non-finite guard
def test_nonfinite_guard_contains_nan():
    seed, rate, n, steps = 13, 0.1, 32, 30
    b = chaos.inject(make_acc(LaneSupervisor(directive=Directive.RESUME),
                              guard=True),
                     seed=seed, nan_rate=rate)
    sys = BatchedSystem(n, [b], payload_width=P)
    sys.spawn_block(0, n)
    sys.run(steps)

    lanes = np.arange(n)
    hits = np.stack([chaos.chaos_hit_np(seed, s, lanes, rate,
                                        chaos.NAN_SALT)
                     for s in range(steps)])
    assert hits.sum() > 0
    acc = sys.read_state("acc")
    assert np.isfinite(acc).all()  # the NaN never landed
    np.testing.assert_array_equal(acc,
                                  (steps - hits.sum(0)).astype(np.float32))
    c = sys.supervision_counts
    assert c["failed"] == c["resumed"] == int(hits.sum())


def test_nonfinite_guard_without_supervisor_sticks():
    b = chaos.inject(make_acc(None, guard=True), seed=13, nan_rate=1.0)
    sys = BatchedSystem(4, [b], payload_width=P)
    sys.spawn_block(0, 4)
    sys.run(3)
    # no supervisor: the error lane is host-mediated, exactly as before
    assert sys.any_failed()
    np.testing.assert_array_equal(sys.failed_rows(), np.arange(4))
    assert np.isfinite(sys.read_state("acc")).all()
    assert sys.supervision_counts["failed"] == 0  # pass not compiled in


# -------------------------------------- satellite 2: host restart_rows
def test_restart_rows_bumps_generation():
    @behavior("cnt", {"acc": ((), jnp.float32)})
    def cnt(state, inbox, ctx):
        return {"acc": state["acc"] + inbox.count}, Emit.none(1, P)

    sys = BatchedSystem(4, [cnt], payload_width=P)
    ids = sys.spawn_block(0, 4)
    g0 = sys.generation_of(ids)

    sys.restart_rows(ids[:1])
    # the restart is a NEW incarnation: a tell whose expect_gen was
    # captured before it must dead-letter, not reach the new occupant
    np.testing.assert_array_equal(sys.generation_of(ids[:1]), g0[:1] + 1)
    sys.tell(int(ids[0]), [1.0] * P, expect_gen=int(g0[0]))
    assert sys.dead_lettered == 1
    sys.run(1)
    assert sys.read_state("acc")[0] == 0.0  # never delivered

    # a tell pinned to the CURRENT generation still lands
    sys.tell(int(ids[0]), [1.0] * P,
             expect_gen=int(sys.generation_of(ids[:1])[0]))
    sys.run(1)
    assert sys.read_state("acc")[0] == 1.0


# ------------------------------------------------- flight recorder hook
def test_supervision_counts_reach_flight_recorder():
    b = chaos.inject(make_acc(LaneSupervisor()), seed=21, crash_rate=0.1)
    sys = BatchedSystem(32, [b], payload_width=P)
    sys.flight_recorder = InMemoryFlightRecorder()
    sys.spawn_block(0, 32)
    sys.run(20)

    evs = sys.flight_recorder.of_type("device_supervision")
    assert evs, "supervision activity must emit a device_supervision event"
    totals = sys.supervision_counts
    assert totals["failed"] > 0
    for name in ("failed", "resumed", "restarted", "stopped", "escalated",
                 "dead_letters"):
        assert sum(e[name] for e in evs) == totals[name]


def test_quiet_system_emits_no_supervision_events():
    sys = BatchedSystem(32, [make_acc(LaneSupervisor())], payload_width=P)
    sys.flight_recorder = InMemoryFlightRecorder()
    sys.spawn_block(0, 32)
    sys.run(20)
    assert sys.flight_recorder.of_type("device_supervision") == []


@pytest.mark.parametrize("crash_rate", [0.0, 1e-2], ids=["quiet", "chaos"])
def test_supervised_ring_counters(crash_rate):
    """The dynamic ring with a LaneSupervisor attached. Quiet: zero
    directive traffic (every counter stays 0) and the ring's counts are
    those of the bare ring. Injected crashes: every failure is restarted
    in-graph, inside one run() scan — no any_failed() poll anywhere."""
    import dataclasses
    from akka_tpu.models.baseline_benches import (PAYLOAD_W, ring_behavior,
                                                  seed_ring_full)
    n, steps = 2048, 12
    b = dataclasses.replace(ring_behavior, supervisor=LaneSupervisor())
    if crash_rate:
        b = chaos.inject(b, seed=7, crash_rate=crash_rate)
    sys = BatchedSystem(n, [b], payload_width=PAYLOAD_W, host_inbox=8)
    sys.spawn_block(0, n)
    seed_ring_full(sys)
    sys.run(steps)
    c = sys.supervision_counts
    if crash_rate:
        assert c["failed"] > 0 and c["restarted"] == c["failed"]
        assert c["stopped"] == c["escalated"] == 0
    else:
        assert not any(c.values())
        assert (sys.read_state("received") == steps).all()
    assert not sys.any_failed()


# ------------------------------------------------------ chaos primitives
def test_chaos_hash_jnp_numpy_parity():
    steps = np.arange(17)[:, None]
    lanes = np.arange(33)[None, :]
    for seed in (0, 1, 0xDEADBEEF):
        for salt in (chaos.CRASH_SALT, chaos.NAN_SALT, chaos.DROP_SALT,
                     chaos.DUP_SALT):
            h_j = np.asarray(jax.device_get(
                chaos.chaos_hash(seed, jnp.asarray(steps),
                                 jnp.asarray(lanes), salt)))
            h_n = (chaos.chaos_uniform_np(seed, steps, lanes, salt)
                   * float(1 << 32)).astype(np.uint32)
            np.testing.assert_array_equal(h_j, h_n)
            for rate in (0.0, 1e-3, 0.25, 1.0):
                hit_j = np.asarray(jax.device_get(chaos.chaos_hit(
                    seed, jnp.asarray(steps), jnp.asarray(lanes), rate,
                    salt)))
                hit_n = chaos.chaos_hit_np(seed, steps, lanes, rate, salt)
                np.testing.assert_array_equal(hit_j, hit_n)


def test_chaos_drop_and_dup_change_traffic_deterministically():
    @behavior("ring", {"received": ((), jnp.int32)}, always_on=True)
    def ring(state, inbox, ctx):
        nxt = (ctx.actor_id + 1) % ctx.n_actors
        return ({"received": state["received"] + inbox.count},
                Emit.single(nxt, jnp.zeros((P,)), 2, P))

    n, steps = 16, 20
    runs = []
    for _ in range(2):  # same seed twice -> identical traffic
        b = chaos.inject(ring, seed=5, drop_rate=0.2, dup_rate=0.2)
        sys = BatchedSystem(n, [b], payload_width=P, out_degree=2)
        sys.spawn_block(0, n)
        sys.run(steps)
        runs.append(sys.read_state("received"))
    np.testing.assert_array_equal(runs[0], runs[1])
    # faults actually fired: traffic differs from the clean run
    clean = BatchedSystem(n, [ring], payload_width=P, out_degree=2)
    clean.spawn_block(0, n)
    clean.run(steps)
    assert not np.array_equal(runs[0], clean.read_state("received"))


# ------------------------------------------- backend / runtime parity
def chaos_ring(sup, slots=False):
    """Token ring under crash chaos: every lane forwards each step, so a
    down lane both loses mail (dead letters) and breaks forwarding —
    maximal pressure on delivery/supervision interaction."""

    @behavior("cring", {"received": ((), jnp.int32)}, always_on=True,
              supervisor=sup, inbox="slots" if slots else "reduce")
    def cring(state, inbox, ctx):
        nxt = (ctx.actor_id + 1) % ctx.n_actors
        count = inbox.count
        return ({"received": state["received"] + count},
                Emit.single(nxt, jnp.zeros((P,)), 1, P))

    return cring


SUP_VARIANTS = {
    "instant": LaneSupervisor(),
    "backoff": LaneSupervisor(min_backoff_steps=2, max_backoff_steps=8),
}


@pytest.mark.parametrize("slots", [0, 4], ids=["reduce", "slots"])
@pytest.mark.parametrize("sup_name", sorted(SUP_VARIANTS))
def test_chaos_seed_parity_across_backends(slots, sup_name):
    """Satellite 4 core claim: the SAME chaos seed on the auto and
    reference delivery backends yields bit-identical state, retry
    counters, and dead-letter counts."""
    n, steps, seed = 64, 40, 77
    outs = []
    for backend in (None, "reference"):
        b = chaos.inject(chaos_ring(SUP_VARIANTS[sup_name],
                                    slots=bool(slots)),
                         seed=seed, crash_rate=0.05)
        sys = BatchedSystem(n, [b], payload_width=P, mailbox_slots=slots,
                            delivery_backend=backend)
        sys.spawn_block(0, n)
        sys.run(steps)
        outs.append({
            "received": sys.read_state("received"),
            "_retries": sys.read_state("_retries"),
            "_restart_at": sys.read_state("_restart_at"),
            "_gen": sys.read_state("_gen"),
            "_failed": sys.read_state("_failed"),
            "counts": sys.supervision_counts,
        })
    auto, ref = outs
    assert auto["counts"] == ref["counts"]
    assert auto["counts"]["failed"] > 0
    for key in ("received", "_retries", "_restart_at", "_gen", "_failed"):
        np.testing.assert_array_equal(auto[key], ref[key], err_msg=key)


def test_sharded_supervision_matches_single_device():
    """Satellite 4: a sharded run where failed lanes sit behind the
    exchange — cross-shard mail to a down lane dead-letters, counters
    aggregate across shards, and the whole run is bit-identical to the
    single-device system."""
    assert jax.device_count() >= 8, "conftest must force 8 CPU devices"
    n, steps, seed = 32, 40, 19
    sup = LaneSupervisor(min_backoff_steps=3, max_backoff_steps=12)

    def build(cls, **kw):
        b = chaos.inject(chaos_ring(sup), seed=seed, crash_rate=0.05)
        sys = cls(capacity=n, behaviors=[b], payload_width=P, **kw)
        sys.spawn_block(0, n)
        sys.run(steps)
        return sys

    single = build(BatchedSystem)
    sharded = build(ShardedBatchedSystem, n_devices=8)

    assert sharded.supervision_counts == single.supervision_counts
    c = single.supervision_counts
    assert c["failed"] > 0 and c["restarted"] > 0
    assert c["dead_letters"] > 0  # down lanes kept receiving ring mail
    for col in ("received", "_retries", "_restart_at", "_gen", "_failed"):
        np.testing.assert_array_equal(sharded.read_state(col),
                                      single.read_state(col), err_msg=col)


# ------------------------------------------- acceptance (slow): 64k lanes
@pytest.mark.slow
@pytest.mark.parametrize("backend", [None, "reference"],
                         ids=["auto", "reference"])
def test_chaos_64k_counters_match_oracle(backend):
    """ISSUE 2 acceptance: 64k actors, crash rate 1e-3/lane/step, 1k
    steps — every recovery handled in-graph (no any_failed() poll on the
    step path) and the counters match the un-jitted oracle EXACTLY."""
    seed, rate, n, steps = 2026, 1e-3, 1 << 16, 1000
    b = chaos.inject(make_acc(LaneSupervisor()), seed=seed, crash_rate=rate)
    sys = BatchedSystem(n, [b], payload_width=P, delivery_backend=backend)
    sys.spawn_block(0, n)
    sys.run(steps)  # ONE scan dispatch: nowhere to hide a host poll

    lanes = np.arange(n)
    o_acc = np.zeros(n)
    failures = 0
    for s in range(steps):
        hit = chaos.chaos_hit_np(seed, s, lanes, rate, chaos.CRASH_SALT)
        o_acc = np.where(hit, 0.0, o_acc + 1.0)
        failures += int(hit.sum())

    c = sys.supervision_counts
    assert failures > 0
    assert c["failed"] == c["restarted"] == failures
    assert c["stopped"] == c["dead_letters"] == 0
    np.testing.assert_array_equal(sys.read_state("acc"),
                                  o_acc.astype(np.float32))
    assert not sys.any_failed()
