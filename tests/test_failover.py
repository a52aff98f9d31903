"""Shard-failure detection and degraded-mesh failover (ISSUE 5).

The acceptance bar mirrors ISSUE 4's honesty standard: a shard killed by
the murmur3 chaos schedule (testkit/chaos.DeviceLossInjector — it freezes
the HOST-OBSERVED attention row, which is exactly the signature a real
preemption leaves) must be detected, evicted, and failed-over by the
MeshSentinel with NO manual restore call, and the run must end
BIT-IDENTICAL to an uninterrupted twin and a numpy oracle on both delivery
backends. Detection runs on an injected manual clock so phi accrual is a
pure function of the schedule, never of host load; MTTR is still measured
with perf_counter.

Seed scanning: the loss schedules are pure murmur3 functions of (seed,
step, shard), so tests SCAN for a seed whose schedule has the shape they
need (exactly one loss, mid-horizon, on the last shard) instead of
hardcoding magic seeds — the predicate documents the scenario. The
last-shard constraint is load-bearing: failover rewinds the observed step
counter to the journal frontier, so a loss scheduled for a LOW shard
index would re-fire when the rebuilt (renumbered) mesh re-crosses that
step. Shard 3 of a 4-shard mesh stops existing after the rebuild; the
mid-backoff test extends the same reasoning to a 2-loss 3->2 cascade.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from akka_tpu.batched import Emit, behavior
from akka_tpu.batched.bridge import RecoveredAskLost
from akka_tpu.batched.sentinel import (MeshSentinel, SentinelHalted,
                                       ShardProgressMonitor)
from akka_tpu.batched.sharded import ShardedBatchedSystem
from akka_tpu.batched.supervision import ATT_PROGRESS, ATT_STEP, ATT_WORDS
from akka_tpu.event.flight_recorder import InMemoryFlightRecorder
from akka_tpu.pattern.ask import AskTimeoutException
from akka_tpu.pattern.circuit_breaker import (CircuitBreaker,
                                              CircuitBreakerOpenException)
from akka_tpu.remote.failure_detector import PhiAccrualFailureDetector
from akka_tpu.testkit import chaos

P = 4
N = 8          # actors
CAP = 48       # divisible by 4, 3, 2, 1: survives any eviction cascade
NDEV = 4
DT = 0.1       # manual-clock seconds per drive iteration

# detector tuning shared by every sentinel in this file: ~4 frozen
# observations at DT cadence push phi past 3.0 (docs/FAILOVER.md)
DETECT = dict(detector_threshold=3.0, heartbeat_interval=DT,
              acceptable_pause=3 * DT)


def make_sum(name="sum"):
    @behavior(name, {"total": ((), jnp.float32)})
    def summer(state, inbox, ctx):
        return {"total": state["total"] + inbox.sum[0]}, Emit.none(1, P)

    return summer


def make_echo(name="echo"):
    """Replies 2x the request's column 0 to the reply row carried in the
    LAST payload column (the ask convention)."""

    @behavior(name, {"seen": ((), jnp.float32)})
    def echo(state, inbox, ctx):
        reply_to = inbox.sum[P - 1].astype(jnp.int32)
        return ({"seen": state["seen"] + inbox.sum[0]},
                Emit.single(reply_to,
                            jnp.stack([inbox.sum[0] * 2.0, 0.0, 0.0, 0.0]),
                            1, P, when=inbox.count > 0))

    return echo


def tell_schedule(seed, n, steps, every=3):
    sched = {}
    for s in range(steps):
        if s % every == 0:
            sched[s] = (int(chaos.chaos_hash(seed, s, 0) % n),
                        float(1 + s % 5))
    return sched


def sum_oracle(sched, n, upto):
    """A tell staged at host step c is delivered by dispatch c+1."""
    out = np.zeros(n, np.float32)
    for s, (dst, val) in sched.items():
        if s <= upto - 1:
            out[dst] += val
    return out


def drive(sent, sched, upto, staged, clk=None, chunk=1, base=0):
    """Step `sent` to host step `upto`, staging scheduled tells at their
    step counters. `staged` persists ACROSS failovers: a failover rewinds
    host_step to the journal frontier and the WAL replay re-stages every
    journaled tell, so the drive loop must not re-tell schedule entries it
    already staged. chunk > 1 exercises the undrained pipeline window
    (drains retire while later programs are already in flight)."""
    while sent.host_step < upto:
        hs = sent.host_step
        if hs in sched and hs not in staged:
            dst, val = sched[hs]
            pl = np.zeros(P, np.float32)
            pl[0] = val
            sent.tell(base + dst, pl)
            staged.add(hs)
        nxt = min([s for s in sched if s > hs and s not in staged] + [upto])
        k = max(1, min(chunk, nxt - hs, upto - hs))
        if clk is not None:
            clk["t"] += DT * k
        sent.step(k)


def pick_single_loss_seed(horizon, rate=0.012, lo=6, hi=16):
    """Seed whose only scheduled loss in the horizon hits the LAST shard
    mid-run (see module docstring for why the last shard)."""
    for seed in range(30000):
        g = chaos.loss_schedule_np(seed, horizon + 1, NDEV, rate)
        hits = np.argwhere(g)
        if (len(hits) == 1 and hits[0][1] == NDEV - 1
                and lo <= hits[0][0] <= hi):
            return seed, int(hits[0][0])
    raise AssertionError("no single-loss seed in scan range")


def make_sentinel(tmp_path, tag, b, clk=None, backend=None, injector=None,
                  fr=None, **kw):
    args = dict(checkpoint_dir=str(tmp_path / tag), n_devices=NDEV,
                payload_width=P, checkpoint_interval_steps=4,
                pipeline_depth=2, failover_min_backoff=0.35,
                delivery_backend=backend, flight_recorder=fr,
                injector=injector, **DETECT)
    if clk is not None:
        args["clock"] = lambda: clk["t"]
    args.update(kw)
    return MeshSentinel(CAP, [b], **args)


# ----------------------------------------------------- chaos schedule parity
def test_loss_schedule_jnp_np_bit_identical():
    for seed in (0, 7, 62, 334, 1999):
        for rate in (0.0, 0.01, 0.2, 1.0):
            j = np.asarray(chaos.loss_schedule(seed, 24, NDEV, rate))
            n = chaos.loss_schedule_np(seed, 24, NDEV, rate)
            np.testing.assert_array_equal(j, n)
            # stall schedule shares the primitive under a different salt
            js = np.asarray(chaos.loss_schedule(seed, 24, NDEV, rate,
                                                salt=chaos.STALL_SALT))
            ns = chaos.loss_schedule_np(seed, 24, NDEV, rate,
                                        salt=chaos.STALL_SALT)
            np.testing.assert_array_equal(js, ns)


def test_disabled_injector_is_identity():
    att = np.arange(NDEV * ATT_WORDS, dtype=np.int64).reshape(NDEV,
                                                              ATT_WORDS)
    off = chaos.DeviceLossInjector(62, NDEV, loss_rate=0.9, stall_rate=0.9,
                                   enabled=False)
    assert off.filter_attention(att) is att  # not even a copy
    zero = chaos.DeviceLossInjector(62, NDEV)
    assert zero.filter_attention(att) is att


def test_injector_freezes_lost_shard_and_thaws_stall():
    seed, t1 = pick_single_loss_seed(horizon=30)
    inj = chaos.DeviceLossInjector(seed, NDEV, loss_rate=0.012)
    rows = []
    for step in range(t1 + 4):
        att = np.zeros((NDEV, ATT_WORDS), np.int64)
        att[:, ATT_STEP] = step
        att[:, ATT_PROGRESS] = step
        rows.append(inj.filter_attention(att))
    # the dying step's completion never reaches the host: the row froze at
    # the last observation BEFORE the scheduled loss step...
    assert rows[-1][NDEV - 1, ATT_PROGRESS] == t1 - 1
    # ...healthy shards pass through untouched
    np.testing.assert_array_equal(rows[-1][: NDEV - 1, ATT_PROGRESS],
                                  np.full(NDEV - 1, t1 + 3))

    # a stall freezes for stall_steps observed steps, then thaws
    sseed = next(s for s in range(10000)
                 if chaos.loss_schedule_np(s, 10, NDEV, 0.02,
                                           salt=chaos.STALL_SALT)[4, 1]
                 and chaos.loss_schedule_np(s, 20, NDEV, 0.02,
                                            salt=chaos.STALL_SALT).sum() == 1)
    stall = chaos.DeviceLossInjector(sseed, NDEV, stall_rate=0.02,
                                     stall_steps=3)
    seen = []
    for step in range(12):
        att = np.zeros((NDEV, ATT_WORDS), np.int64)
        att[:, ATT_STEP] = step
        att[:, ATT_PROGRESS] = step
        seen.append(int(stall.filter_attention(att)[1, ATT_PROGRESS]))
    assert seen[4] == seen[5] == seen[6] == 3   # frozen window [4, 6]
    assert seen[7] == 7                          # thawed


# ---------------------------------------------------------- quiet-path parity
@pytest.mark.parametrize("backend", [None, "reference"])
def test_quiet_parity_disabled_injector(tmp_path, backend):
    """A disabled injector (and an armed-but-never-firing sentinel) is
    bit-invisible: same totals, same attention words, same counters as a
    sentinel with no injector at all."""
    seed, horizon = 5, 12
    sched = tell_schedule(seed, N, horizon)
    off = chaos.DeviceLossInjector(62, NDEV, loss_rate=0.9, enabled=False)
    runs = []
    for tag, inj in (("armed", off), ("bare", None)):
        clk = {"t": 0.0}
        s = make_sentinel(tmp_path, f"{tag}-{backend}", make_sum(), clk=clk,
                          backend=backend, injector=inj)
        rows = s.spawn(0, N)
        drive(s, sched, horizon, set(), clk=clk)
        runs.append((np.asarray(s.read_state("total", rows)),
                     np.asarray(jax.device_get(s.system.attention)),
                     np.asarray(s.system.dropped_per_shard),
                     np.asarray(s.system.mailbox_overflow_per_shard),
                     s.sentinel_stats()["failovers"]))
        s.shutdown()
    for a, b in zip(runs[0], runs[1]):
        np.testing.assert_array_equal(a, b)
    assert runs[0][4] == 0
    np.testing.assert_array_equal(runs[0][0], sum_oracle(sched, N, horizon))


# -------------------------------------------------- phi detector (satellite 1)
def test_phi_default_clock_is_monotonic():
    # wall-clock (time.time) is NTP-steerable; the detector must default
    # to the monotonic clock so a clock jump cannot fake a failure
    assert PhiAccrualFailureDetector().clock is time.monotonic
    assert ShardProgressMonitor().clock is time.monotonic


def test_phi_manual_clock_ntp_jump_regression():
    clk = {"t": 0.0}
    fd = PhiAccrualFailureDetector(threshold=3.0, min_std_deviation=0.025,
                                   acceptable_heartbeat_pause=0.3,
                                   first_heartbeat_estimate=0.1,
                                   clock=lambda: clk["t"])
    for _ in range(20):
        fd.heartbeat()
        clk["t"] += 0.1
    # steady cadence on the injected clock: available, phi calm — and a
    # wall-clock jump CANNOT reach this detector, because it never reads
    # wall time (the jump below is what an NTP step would do to a
    # wall-clock-backed detector, proving why the default is monotonic)
    assert fd.is_available and fd.phi() < 1.0
    clk["t"] += 3600.0
    assert not fd.is_available and fd.phi() > 3.0


# --------------------------------------------- circuit breaker (satellite 2)
def test_half_open_admits_exactly_one_probe_and_reopens_atomically():
    cb = CircuitBreaker(None, max_failures=1, call_timeout=10.0,
                        reset_timeout=0.05, exponential_backoff_factor=2.0,
                        max_reset_timeout=10.0)
    with pytest.raises(RuntimeError):
        cb.call(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    assert cb.state == "open"
    time.sleep(0.06)
    assert cb.state == "half-open"

    probe_started = threading.Event()
    outcomes = {}

    def probe():
        probe_started.set()
        time.sleep(0.15)  # hold the permit while the rival attempts
        raise RuntimeError("probe fails")

    def run_probe():
        try:
            cb.call(probe)
        except Exception as e:  # noqa: BLE001
            outcomes["probe"] = e

    def run_rival():
        probe_started.wait(2.0)
        try:
            cb.call(lambda: outcomes.setdefault("rival_ran", True))
        except Exception as e:  # noqa: BLE001
            outcomes["rival"] = e

    t1 = threading.Thread(target=run_probe)
    t2 = threading.Thread(target=run_rival)
    t1.start(); t2.start(); t1.join(); t2.join()

    # exactly ONE probe was admitted; the rival failed fast on the permit
    assert "rival_ran" not in outcomes
    assert isinstance(outcomes["rival"], CircuitBreakerOpenException)
    assert isinstance(outcomes["probe"], RuntimeError)
    # the raising probe re-opened atomically: backoff doubled AND the
    # reset timer restarted (remaining > the original 0.05s budget)
    assert cb.state == "open"
    assert cb._current_reset == pytest.approx(0.1)
    with pytest.raises(CircuitBreakerOpenException) as ei:
        cb.call(lambda: None)
    assert ei.value.remaining > 0.05


# ------------------------------------------- per-shard overflow (satellite 3)
@pytest.mark.parametrize("n_dev", [2, 4])
def test_per_shard_overflow_counters_and_event(n_dev):
    n = 64

    @behavior("spam", {}, always_on=True)
    def spam(state, inbox, ctx):
        return {}, Emit.single(0, jnp.array([1.0, 0, 0, 0]), 1, 4)

    fr = InMemoryFlightRecorder()
    sys_ = ShardedBatchedSystem(capacity=n, behaviors=[spam],
                                n_devices=n_dev, remote_capacity_per_pair=2)
    sys_.flight_recorder = fr
    sys_.spawn_block(spam, n)
    sys_.run(3)
    word = sys_.read_attention()
    per_shard = np.asarray(sys_.dropped_per_shard)
    assert per_shard.shape == (n_dev,)
    assert per_shard.sum() == sys_.total_dropped > 0
    np.testing.assert_array_equal(per_shard, word["dropped_per_shard"])
    assert sys_.mailbox_overflow_per_shard.shape == (n_dev,)
    events = fr.of_type("shard_overflow")
    assert events, "overflow growth must emit a shard_overflow warning"
    assert {e["shard"] for e in events} <= set(range(n_dev))
    assert all(e["dropped"] > 0 for e in events)
    n_first = len(events)
    sys_.read_attention()  # no growth since last read -> no new events
    assert len(fr.of_type("shard_overflow")) == n_first


# --------------------------------------------------- the tentpole acceptance
@pytest.mark.parametrize("backend,phase", [(None, "staging"),
                                           ("reference", "pipeline-full")])
def test_auto_failover_bit_parity(tmp_path, backend, phase):
    """Chaos kills a shard mid-run; the sentinel detects it from the frozen
    progress lane, evicts, rebuilds on 3 devices from snapshot + WAL, and
    finishes BIT-IDENTICAL to an uninterrupted twin and the numpy oracle —
    no manual restore call anywhere."""
    horizon = 40
    seed, t1 = pick_single_loss_seed(horizon)
    sched = tell_schedule(seed, N, horizon)
    chunk = 1 if phase == "staging" else 3

    clk = {"t": 0.0}
    fr = InMemoryFlightRecorder()
    inj = chaos.DeviceLossInjector(seed, NDEV, loss_rate=0.012)
    victim = make_sentinel(tmp_path, f"victim-{backend}-{phase}", make_sum(),
                           clk=clk, backend=backend, injector=inj, fr=fr,
                           pipeline_depth=(3 if phase == "pipeline-full"
                                           else 2))
    vrows = victim.spawn(0, N)
    drive(victim, sched, horizon, set(), clk=clk, chunk=chunk)

    stats = victim.sentinel_stats()
    assert stats["failovers"] == 1 and stats["halted"] is None
    assert len(victim.devices) == NDEV - 1
    assert victim.system.n_shards == NDEV - 1
    st = victim.failover_stats[0]
    assert st["lost_shards"] == [NDEV - 1]
    assert st["detector"] == "phi-accrual"
    assert st["evicted_at_step"] >= t1  # cannot evict before the loss fires
    assert st["mttr_s"] is not None and st["mttr_s"] > 0
    names = [e["event"] for e in fr.events()]
    for ev in ("device_suspected", "device_evicted", "failover_completed"):
        assert ev in names

    # uninterrupted twin (identical machinery, no injector) and the oracle
    tclk = {"t": 0.0}
    twin = make_sentinel(tmp_path, f"twin-{backend}-{phase}", make_sum(),
                         clk=tclk, backend=backend,
                         pipeline_depth=(3 if phase == "pipeline-full"
                                         else 2))
    trows = twin.spawn(0, N)
    drive(twin, sched, horizon, set(), clk=tclk, chunk=chunk)
    assert twin.sentinel_stats()["failovers"] == 0

    truth = np.asarray(twin.read_state("total", trows))
    np.testing.assert_array_equal(truth, sum_oracle(sched, N, horizon))
    got = np.asarray(victim.read_state("total", vrows))
    np.testing.assert_array_equal(got, truth)
    # the degraded mesh keeps heartbeating: 3 live progress lanes
    word = victim.read_attention()
    assert word["progress_per_shard"].shape == (NDEV - 1,)
    assert (word["progress_per_shard"] > 0).all()
    victim.shutdown()
    twin.shutdown()


def test_mid_backoff_second_loss_cascades_to_two_devices(tmp_path):
    """A second loss landing inside the post-failover backoff window is
    DEFERRED (suspicion withdrawn, no event), then acted on once the
    window closes: 4 -> 3 -> 2 devices, depth degraded, still oracle-exact."""
    horizon, rate = 60, 0.012
    seed = t1 = t2 = None
    for cand in range(30000):
        g = chaos.loss_schedule_np(cand, horizon + 1, NDEV, rate)
        hits = sorted((int(t), int(s)) for t, s in np.argwhere(g))
        if (len(hits) == 2 and hits[0][1] == 3 and hits[1][1] == 2
                and 6 <= hits[0][0] <= 14
                and hits[0][0] + 10 <= hits[1][0] <= hits[0][0] + 16):
            seed, t1, t2 = cand, hits[0][0], hits[1][0]
            break
    assert seed is not None
    sched = tell_schedule(seed, N, horizon)

    clk = {"t": 0.0}
    fr = InMemoryFlightRecorder()
    inj = chaos.DeviceLossInjector(seed, NDEV, loss_rate=rate)
    s = make_sentinel(tmp_path, "cascade", make_sum(), clk=clk, injector=inj,
                      fr=fr, failover_min_backoff=1.2, max_failovers=5)
    rows = s.spawn(0, N)
    drive(s, sched, horizon, set(), clk=clk)

    stats = s.sentinel_stats()
    assert stats["failovers"] == 2 and stats["halted"] is None
    assert len(s.devices) == 2 and s.system.n_shards == 2
    # deferral emitted NO extra suspicion events: one per acted-on loss
    assert len(fr.of_type("device_suspected")) == 2
    assert [e["shard"] for e in fr.of_type("device_evicted")] == [3, 2]
    # the second eviction waited out the backoff window (deferred, then
    # acted on): at least backoff_delay(1, 1.2, ...) = 2.4 clock-seconds
    # separate the failovers even though the loss fired well inside it
    f1, f2 = s.failover_stats
    assert f2["at_clock"] - f1["at_clock"] >= 2.4
    assert f2["pipeline_depth"] < f1["pipeline_depth"]  # degrade ladder
    np.testing.assert_array_equal(np.asarray(s.read_state("total", rows)),
                                  sum_oracle(sched, N, horizon))
    s.shutdown()


# ------------------------------------------------------------- ask semantics
def test_ask_resolves_times_out_and_fails_fast_on_failover(tmp_path):
    clk = {"t": 0.0}
    echo = make_echo()
    s = make_sentinel(tmp_path, "ask", echo, clk=clk, promise_rows=8)
    assert s.system._core.delivery == "scatter"  # bridge.ASK_DELIVERY
    rows = s.spawn(0, N)

    fut = s.ask(int(rows[2]), np.array([21.0, 0, 0], np.float32),
                timeout=50.0)
    clk["t"] += 2 * DT
    s.step(2)  # deliver, reply, latch, drain-resolve
    assert fut.done() and float(fut.result()[0]) == 42.0

    # timeout: target row N-1 never replies (asks to a dead row must not
    # hang) — the sentinel clock drives the deadline
    dead_fut = s.ask(int(rows[0]) + CAP // 2, np.array([1.0], np.float32),
                     timeout=0.5)
    for _ in range(8):
        clk["t"] += DT
        s.step(1)
    assert isinstance(dead_fut.exception(), AskTimeoutException)

    # failover: an outstanding ask fails FAST with RecoveredAskLost
    lost_fut = s.ask(int(rows[3]), np.array([7.0, 0, 0], np.float32),
                     timeout=50.0)
    s.force_evict([NDEV - 1])
    assert isinstance(lost_fut.exception(), RecoveredAskLost)
    assert s.system._core.delivery == "scatter"  # the rebuild keeps it
    # the rebuilt system still answers fresh asks
    fut2 = s.ask(int(rows[2]), np.array([4.0, 0, 0], np.float32),
                 timeout=50.0)
    clk["t"] += 2 * DT
    s.step(2)
    assert float(fut2.result()[0]) == 8.0
    s.shutdown()


def test_forced_eviction_is_one_eviction_and_one_completion(tmp_path):
    """One forced eviction mid-run: exactly one device_evicted and one
    failover_completed reach the flight recorder, the first drain on the
    survivors closes the MTTR clock, and the sums stay the oracle's."""
    horizon = 24
    sched = tell_schedule(3, N, horizon)
    fr = InMemoryFlightRecorder()
    s = make_sentinel(tmp_path, "forced", make_sum(), fr=fr,
                      failover_min_backoff=0.01, failover_max_backoff=0.01)
    rows = s.spawn(0, N)
    staged = set()
    drive(s, sched, horizon // 2, staged)
    s.force_evict([NDEV - 1], detector="test")
    drive(s, sched, horizon, staged)
    assert len(fr.of_type("device_evicted")) == 1
    assert len(fr.of_type("failover_completed")) == 1
    stats = s.sentinel_stats()
    assert stats["failovers"] == 1 and stats["halted"] is None
    fo = stats["failover_stats"][-1]
    assert fo["detector"] == "test" and fo["lost_shards"] == [NDEV - 1]
    assert fo["mttr_s"] is not None and fo["mttr_s"] > 0
    np.testing.assert_array_equal(np.asarray(s.read_state("total", rows)),
                                  sum_oracle(sched, N, horizon))
    s.shutdown()


# ------------------------------------------------------ degrade-to-halt path
def test_repeated_failovers_trip_breaker_into_halt(tmp_path):
    clk = {"t": 0.0}
    fr = InMemoryFlightRecorder()
    s = make_sentinel(tmp_path, "halt", make_sum(), clk=clk, fr=fr,
                      max_failovers=2, pipeline_depth=4,
                      failover_min_backoff=0.01)
    rows = s.spawn(0, N)
    s.tell(int(rows[0]), np.array([1.0, 0, 0, 0], np.float32))
    s.step(2)

    s.force_evict([3])     # failover 1: 4 -> 3
    assert s.pipeline_depth == 4
    s.step(1)
    s.force_evict([2])     # failover 2: 3 -> 2, depth halves, breaker trips
    assert s.pipeline_depth == 2
    assert len(s.devices) == 2
    s.step(1)

    s.force_evict([1])     # breaker open: degrade to HALT, not failover 3
    assert s.halted is not None
    assert s.sentinel_stats()["failovers"] == 2
    halted = fr.of_type("failover_halted")
    assert len(halted) == 1 and halted[0]["failovers"] == 2
    with pytest.raises(SentinelHalted):
        s.step(1)
    with pytest.raises(SentinelHalted):
        s.tell(int(rows[0]), np.array([1.0, 0, 0, 0], np.float32))
    s.shutdown()


# ------------------------------------------------- deadline lane (hung pump)
def test_monitor_deadline_suspects_stalest_shard():
    clk = {"t": 0.0}
    mon = ShardProgressMonitor(threshold=3.0, heartbeat_interval=0.1,
                               acceptable_pause=0.3,
                               clock=lambda: clk["t"])
    att = np.zeros((NDEV, ATT_WORDS), np.int64)
    for step in range(1, 6):
        att[:, ATT_PROGRESS] = step
        att[2, ATT_PROGRESS] = 1  # shard 2 lags from the start
        assert mon.observe(att) == []
        clk["t"] += 0.1
    assert mon.check_deadline() is None  # observations are flowing
    # total drain silence: no observe() at all past the deadline — phi has
    # no new words to accrue on, only the wall clock can see this
    clk["t"] += 10.0
    hit = mon.check_deadline()
    assert hit is not None
    shard, phi, detector = hit
    assert shard == 2 and detector == "deadline"  # stalest lane is blamed
    assert mon.check_deadline() is None  # suspicion fires once
    mon.reset()
    assert mon.suspected() == set()


def test_monitor_unsuspect_defers_then_retrips():
    """The backoff-window deferral contract: withdrawn suspicion re-trips
    on the next observation while the lane is still frozen."""
    clk = {"t": 0.0}
    mon = ShardProgressMonitor(threshold=3.0, heartbeat_interval=0.1,
                               acceptable_pause=0.3,
                               clock=lambda: clk["t"])
    att = np.zeros((NDEV, ATT_WORDS), np.int64)
    newly = []
    for step in range(1, 12):
        att[:, ATT_PROGRESS] = step
        att[1, ATT_PROGRESS] = min(step, 2)  # shard 1 freezes at step 2
        clk["t"] += 0.1
        newly = mon.observe(att)
        if newly:
            break
    assert [s for s, _, _ in newly] == [1]
    assert mon.observe(att) == []        # suspicion latches: no re-report
    mon.unsuspect([1])                   # deferred by the backoff window
    clk["t"] += 0.1
    again = mon.observe(att)             # still frozen: trips again
    assert [s for s, _, _ in again] == [1]


def test_sentinel_poll_drives_deadline_eviction(tmp_path):
    clk = {"t": 0.0}
    fr = InMemoryFlightRecorder()
    s = make_sentinel(tmp_path, "poll", make_sum(), clk=clk, fr=fr)
    assert s.system._core.delivery == "auto"  # no promise rows, no asks
    s.spawn(0, N)
    for _ in range(3):
        clk["t"] += DT
        s.step(1)
    s.poll()
    assert s.sentinel_stats()["failovers"] == 0  # healthy: poll is a no-op
    clk["t"] += 10.0  # pump goes silent past the deadline
    s.poll()
    assert s.sentinel_stats()["failovers"] == 1
    assert fr.of_type("device_suspected")[0]["detector"] == "deadline"
    assert len(s.devices) == NDEV - 1
    s.shutdown()


# ------------------------------------------------------------ config surface
def test_config_wires_sentinel_keys(tmp_path):
    from akka_tpu.config import Config, reference_config
    from akka_tpu.dispatch.batched import TpuBatchedDispatcher

    class _Disp:
        pass

    ref = reference_config()
    base = "akka.actor.tpu-dispatcher"
    assert ref.get_float(f"{base}.sentinel-threshold", 0.0) == 8.0
    assert ref.get_int(f"{base}.sentinel-max-failovers", 0) == 3

    cfg = Config({"capacity": 64, "payload-width": 8, "promise-rows": 8,
                  "sentinel-threshold": 5.5,
                  "sentinel-heartbeat-interval": "50ms",
                  "sentinel-acceptable-pause": "2s",
                  "sentinel-max-failovers": 7})
    d = TpuBatchedDispatcher(_Disp(), "tpu-dispatcher", cfg)
    h = d.handle()
    assert h._sentinel.threshold == 5.5
    assert h._sentinel.heartbeat_interval == pytest.approx(0.05)
    assert h._sentinel.acceptable_pause == pytest.approx(2.0)
    assert h.sentinel_max_failovers == 7
    assert h.sentinel_stats()["max_failovers"] == 7
    h.shutdown()
