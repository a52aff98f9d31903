"""Native substrate tests: C++ MPSC queue, hashed-wheel timer, message
stager, and their runtime integrations — the equivalents of the reference's
dispatcher/queue stress tests (akka-actor-tests ConsistencySpec,
SystemMessageListSpec) for our native layer."""

import threading
import time

import numpy as np
import pytest

from akka_tpu import ActorSystem, Props
from akka_tpu.actor.actor import Actor
from akka_tpu.native import available
from akka_tpu.testkit import TestProbe

pytestmark = pytest.mark.skipif(not available(),
                                reason="native library not built (no g++?)")

CFG = {"akka": {"stdout-loglevel": "OFF", "log-dead-letters": 0}}


def test_mpsc_queue_fifo_single_thread():
    from akka_tpu.native.queues import NativeMpscQueue
    q = NativeMpscQueue()
    for i in range(100):
        q.enqueue(("msg", i))
    assert len(q) == 100
    out = []
    while True:
        m = q.dequeue()
        if m is None:
            break
        out.append(m[1])
    assert out == list(range(100))
    q.close()


def test_mpsc_queue_many_producers_one_consumer():
    """The MPSC contract under real thread contention (ConsistencySpec's
    job: no loss, no duplication)."""
    from akka_tpu.native.queues import NativeMpscQueue
    q = NativeMpscQueue()
    n_producers, per = 8, 2000

    def produce(pid):
        for i in range(per):
            q.enqueue((pid, i))

    threads = [threading.Thread(target=produce, args=(p,))
               for p in range(n_producers)]
    for t in threads:
        t.start()
    seen = []
    deadline = time.monotonic() + 15
    while len(seen) < n_producers * per and time.monotonic() < deadline:
        m = q.dequeue()
        if m is None:
            time.sleep(0.0005)
            continue
        seen.append(m)
    for t in threads:
        t.join()
    assert len(seen) == n_producers * per
    assert len(set(seen)) == n_producers * per  # no duplication
    # per-producer FIFO preserved
    for p in range(n_producers):
        mine = [i for (pid, i) in seen if pid == p]
        assert mine == list(range(per))
    q.close()


def test_wheel_timer_fires_and_cancels():
    from akka_tpu.native.queues import NativeWheelTimer
    t = NativeWheelTimer(tick_duration=0.001)
    fired = []
    t.schedule_once(0.02, lambda: fired.append("once"))
    tid = t.schedule_once(0.5, lambda: fired.append("cancelled"))
    t.cancel(tid)
    periodic_count = []
    pid = t.schedule_periodically(0.01, 0.02, lambda: periodic_count.append(1))
    time.sleep(0.3)
    t.cancel(pid)
    assert "once" in fired
    assert "cancelled" not in fired
    assert len(periodic_count) >= 3
    n_at_cancel = len(periodic_count)
    time.sleep(0.1)
    assert len(periodic_count) <= n_at_cancel + 1  # stops after cancel
    t.shutdown()


def test_stager_stage_and_drain():
    from akka_tpu.native.queues import NativeStager
    s = NativeStager(64, 4, np.float32)
    s.stage(np.array([1, 2], np.int32),
            np.array([[1, 0, 0, 0], [2, 0, 0, 0]], np.float32))
    s.stage(np.array([3], np.int32), np.array([[3, 0, 0, 0]], np.float32))
    assert len(s) == 3
    dst, pl = s.drain()
    assert dst.tolist() == [1, 2, 3]
    assert pl[:, 0].tolist() == [1.0, 2.0, 3.0]
    assert len(s) == 0
    # overflow drops whole batches, keeps count
    big = np.zeros(100, np.int32)
    assert s.stage(big, np.zeros((100, 4), np.float32)) == 0
    assert s.dropped >= 100
    s.close()


def test_stager_concurrent_producers():
    from akka_tpu.native.queues import NativeStager
    s = NativeStager(64 * 1024, 4, np.float32)
    n_threads, per = 8, 500

    def produce(tid):
        for i in range(per):
            s.stage(np.array([tid * per + i], np.int32),
                    np.array([[float(tid)] * 4], np.float32))

    threads = [threading.Thread(target=produce, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dst, pl = s.drain()
    assert dst.shape[0] == n_threads * per
    assert len(set(dst.tolist())) == n_threads * per  # every slot distinct
    s.close()


def test_native_mailbox_in_actor_system():
    system = ActorSystem.create("native-mb", {
        "akka": {"stdout-loglevel": "OFF", "log-dead-letters": 0,
                 "actor": {"native-mailboxes": True}}})
    try:
        probe = TestProbe(system)

        class Echo(Actor):
            def receive(self, message):
                self.sender.tell(message * 2, self.self_ref)

        ref = system.actor_of(Props(factory=Echo, cls=Echo,
                                    mailbox="native-unbounded"), "necho")
        for i in range(50):
            ref.tell(i, probe.ref)
        got = [probe.receive_one(5.0) for _ in range(50)]
        assert got == [i * 2 for i in range(50)]  # FIFO through native queue
    finally:
        system.terminate()
        system.await_termination(10.0)


def test_native_scheduler_in_actor_system():
    system = ActorSystem.create("native-sched", {
        "akka": {"stdout-loglevel": "OFF", "log-dead-letters": 0,
                 "scheduler": {"implementation": "native",
                               "tick-duration": "1ms"}}})
    try:
        from akka_tpu.native.integration import NativeScheduler
        assert isinstance(system.scheduler, NativeScheduler)
        probe = TestProbe(system)
        system.scheduler.schedule_tell_once(0.03, probe.ref, "tick")
        assert probe.receive_one(5.0) == "tick"
        c = system.scheduler.schedule_tell_with_fixed_delay(
            0.01, 0.02, probe.ref, "beat")
        assert probe.receive_one(5.0) == "beat"
        assert probe.receive_one(5.0) == "beat"
        c.cancel()
    finally:
        system.terminate()
        system.await_termination(10.0)


def test_batched_system_uses_native_stager():
    from akka_tpu.models.baseline_benches import build_ring
    sys_ = build_ring(64)
    assert sys_._stager is not None  # a stager that cannot build raises
    # host tells ride the native stager into the inbox
    sys_.tell(np.arange(8), np.ones((8, 4), np.float32))
    assert len(sys_._stager) == 8
    sys_._flush_staged()
    assert len(sys_._stager) == 0
    import numpy as _np
    valid = _np.asarray(sys_.inbox_valid)
    base = sys_.spill_cap + sys_.capacity * sys_.out_degree
    assert valid[base:base + 8].all()


def _aligned(shape, dtype, fill=0):
    """A numpy array whose data pointer is 64-byte aligned — the case in
    which jnp.asarray aliases the host buffer on CPU instead of copying."""
    n = int(np.prod(shape)) * np.dtype(dtype).itemsize
    raw = np.zeros(n + 64, np.uint8)
    off = (-raw.ctypes.data) % 64
    arr = raw[off:off + n].view(dtype).reshape(shape)
    arr[...] = fill
    assert arr.ctypes.data % 64 == 0
    return arr


class _AlignedNumpy:
    """numpy as batched/core.py sees it, except that every zeros/full
    allocation is 64-byte aligned: wherever core allocates its host pads,
    and whenever (per flush, or once at construction), they alias."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def zeros(shape, dtype=float):
        return _aligned(shape, dtype)

    @staticmethod
    def full(shape, fill_value, dtype=None):
        return _aligned(shape, dtype or np.asarray(fill_value).dtype,
                        fill_value)


class _AuditedJnp:
    """jax.numpy as batched/core.py sees it, except that every numpy array
    handed to jnp.asarray is kept (so its address stays taken) next to a
    copy of what it held at the hand-off."""

    def __init__(self, jnp):
        self._jnp = jnp
        self.handed = []  # (the array itself, its contents at hand-off)

    def __getattr__(self, name):
        return getattr(self._jnp, name)

    def asarray(self, a, *args, **kw):
        if isinstance(a, np.ndarray):
            self.handed.append((a, a.copy()))
        return self._jnp.asarray(a, *args, **kw)


@pytest.mark.parametrize("native", [True, False])
def test_staged_tells_survive_back_to_back_dispatch(monkeypatch, native):
    """ISSUE 22 item 3: every flush dispatch owns the host pads it was
    given. With 64-byte-aligned pads jnp.asarray hands the program the
    numpy buffer itself, and dispatch is asynchronous — so staging and
    dispatching the NEXT batch before the previous program ran used to
    overwrite what that program had not read yet (tells lost or delivered
    to the wrong row). Nothing here is left to the allocator or to timing:
    alignment is forced where core allocates (before the system exists, so
    pads cached at construction would alias too), and besides the totals
    the test audits the hand-off itself — no buffer given to JAX is
    written again, none is given twice."""
    import jax.numpy as jnp
    from akka_tpu.batched import BatchedSystem, Emit, behavior, core

    @behavior("acc", {"total": ((), jnp.float32), "n": ((), jnp.int32)})
    def acc(state, inbox, ctx):
        return ({"total": state["total"] + inbox.sum[0],
                 "n": state["n"] + inbox.count}, Emit.none(1, 4))

    audit = _AuditedJnp(jnp)
    monkeypatch.setattr(core, "np", _AlignedNumpy())
    monkeypatch.setattr(core, "jnp", audit)
    s = BatchedSystem(64, [acc], payload_width=4, host_inbox=64,
                      native_staging=native)
    s.spawn_block(acc, 64)
    h = s.host_inbox
    audit.handed.clear()  # spawn's own hand-offs are not flush pads
    rng = np.random.default_rng(22)
    want = np.zeros((64,), np.float64)
    count = np.zeros((64,), np.int64)
    rounds = 48
    for _ in range(rounds):  # stage, dispatch, stage the next: never a sync
        k = int(rng.integers(1, h))
        dst = rng.integers(0, 64, size=k)
        val = rng.integers(1, 9, size=k).astype(np.float32)
        pl = np.zeros((k, 4), np.float32)
        pl[:, 0] = val
        s.tell(dst, pl)
        np.add.at(want, dst, val)
        np.add.at(count, dst, 1)
        s.step()
    # still no sync: audit the hand-offs before anything is read back
    pads = [(a, was) for a, was in audit.handed if a.shape[:1] == (h,)]
    assert len(pads) == 4 * rounds  # dst, type, payload, valid per flush
    assert all(a.ctypes.data % 64 == 0 for a, _ in pads)  # they did alias
    assert len({a.ctypes.data for a, _ in pads}) == len(pads), \
        "a host pad was handed to a second dispatch"
    for a, was in pads:
        np.testing.assert_array_equal(
            a, was, err_msg="a host pad was written after its dispatch")
    assert s.dropped_messages == 0
    np.testing.assert_array_equal(s.read_state("total"), want)
    np.testing.assert_array_equal(s.read_state("n"), count)


def test_wheel_timer_interval_exact_wheel_multiple():
    """Regression (ADVICE r1): a periodic interval that is an exact multiple
    of the wheel size used to be re-appended into the slot being iterated
    with rounds==0, firing and re-appending forever (tick thread livelock
    while holding the wheel mutex). With absolute deadlines + deferred
    reschedule, it must fire once per interval and stay responsive."""
    from akka_tpu.native.queues import NativeWheelTimer
    # wheel_size=8 ticks of 2ms -> one revolution = 16ms; interval = exactly
    # one revolution (and a second timer at two revolutions)
    t = NativeWheelTimer(tick_duration=0.002, wheel_size=8)
    one_rev, two_rev = [], []
    p1 = t.schedule_periodically(0.016, 0.016, lambda: one_rev.append(1))
    p2 = t.schedule_periodically(0.032, 0.032, lambda: two_rev.append(1))
    time.sleep(0.25)
    # schedule/cancel must not block (the old bug hung the mutex)
    start = time.monotonic()
    t.cancel(p1)
    t.cancel(p2)
    assert time.monotonic() - start < 1.0
    # ~15 one-rev fires in 250ms; the bug produced hundreds (or a hang)
    assert 5 <= len(one_rev) <= 25
    # two-revolution interval must NOT fire one revolution early
    assert 3 <= len(two_rev) <= 12
    t.shutdown()


def test_mpsc_close_races_with_producers_and_consumer():
    """Regression (ADVICE r1): close() while producers are mid-tell and the
    consumer is mid-dequeue must not free or drain under them (close is
    flag-only; reclamation deferred to __del__). Late enqueues are safe
    no-ops that leave no registry garbage."""
    from akka_tpu.native.queues import NativeMpscQueue
    for _ in range(5):
        q = NativeMpscQueue()
        stop = threading.Event()
        consumed = []

        def produce():
            i = 0
            while not stop.is_set():
                q.enqueue(i)
                i += 1

        def consume():
            while not stop.is_set():
                m = q.dequeue()
                if m is not None:
                    consumed.append(m)

        threads = [threading.Thread(target=produce) for _ in range(4)]
        threads.append(threading.Thread(target=consume))
        for th in threads:
            th.start()
        time.sleep(0.01)
        q.close()  # producers AND the consumer still running
        time.sleep(0.01)
        stop.set()
        for th in threads:
            th.join()
        # post-close enqueues are rejected (caller dead-letters) and leave
        # no lasting registry entries — this is the real state check, not
        # the flag-shortcircuited len()/dequeue()
        before = len(q._registry)
        assert q.enqueue("late-1") is False
        assert q.enqueue("late-2") is False
        assert len(q._registry) == before
        # __del__ reclaims the native queue + pending nodes without crashing
        del q


def test_late_tell_to_stopped_native_mailbox_goes_to_dead_letters():
    """becomeClosed parity: a tell to a stopped actor with a native mailbox
    must surface as a DeadLetter on the event stream, never vanish."""
    from akka_tpu.actor.messages import DeadLetter, PoisonPill
    system = ActorSystem.create("native-dl", {
        "akka": {"stdout-loglevel": "OFF", "log-dead-letters": 0,
                 "actor": {"native-mailboxes": True}}})
    try:
        probe = TestProbe(system)
        system.event_stream.subscribe(probe.ref, DeadLetter)

        class Sink(Actor):
            def receive(self, message):
                pass

        ref = system.actor_of(Props(factory=Sink, cls=Sink,
                                    mailbox="native-unbounded"), "sink")
        stop_probe = TestProbe(system)
        stop_probe.watch(ref)
        ref.tell(PoisonPill, None)
        stop_probe.expect_terminated(ref, 5.0)
        ref.tell("too-late", probe.ref)
        dl = probe.receive_one(5.0)
        assert isinstance(dl, DeadLetter)
        assert dl.message == "too-late"
    finally:
        system.terminate()
        system.await_termination(10.0)


def test_stager_stage_during_drain_never_drops():
    """Regression: a stage() racing an in-flight drain() used to hit the
    cursor fence and drop the whole batch as phantom 'overflow'. Stages must
    wait out the drain; only a genuinely full buffer drops."""
    from akka_tpu.native.queues import NativeStager
    s = NativeStager(8192, 4, np.float32)
    total = [0]
    stop = threading.Event()

    def produce():
        while not stop.is_set():
            got = s.stage(np.array([1], np.int32),
                          np.ones((1, 4), np.float32))
            total[0] += got

    drained = [0]
    threads = [threading.Thread(target=produce) for _ in range(4)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 1.0
    while time.monotonic() < deadline:
        dst, _ = s.drain()
        drained[0] += dst.shape[0]
    stop.set()
    for t in threads:
        t.join()
    dst, _ = s.drain()
    drained[0] += dst.shape[0]
    # every accepted stage is eventually drained; nothing vanished into the
    # drop counter from drain fencing (the buffer never filled: 8192 >> rate)
    assert s.dropped == 0, s.dropped
    assert drained[0] == total[0]
    s.close()
