"""The tpu-batched dispatcher bridge: ActorRef.tell -> device rows (VERDICT
r1 item 2).

Covers the reference seam being replaced: Dispatchers type selection
(dispatch/Dispatchers.scala:121-259), the tell hot path (SURVEY.md §3.2) and
ask via promise refs (pattern/AskSupport.scala:476) — all against the device
runtime through the PUBLIC ActorSystem API.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from akka_tpu import ActorSystem
from akka_tpu.batched import (DeviceActorRef, DeviceBlockRef, Emit, Mailbox,
                              behavior, device_props, get_handle, reply_dst)
from akka_tpu.pattern.ask import ask_sync

F32, I32 = jnp.float32, jnp.int32

ADD, GET = 0, 1

CFG = {"akka": {"stdout-loglevel": "OFF", "log-dead-letters": 0,
                "actor": {"tpu-dispatcher": {
                    "capacity": 1 << 12, "payload-width": 4,
                    "mailbox-slots": 4, "host-inbox": 8192,
                    "promise-rows": 32}}}}


@behavior("counter", {"count": ((), F32)}, inbox="slots")
def counter(state, mailbox: Mailbox, ctx):
    def apply(carry, t, pl):
        cnt, rdst = carry
        return (jnp.where(t == ADD, cnt + pl[0], cnt),
                jnp.where(t == GET, reply_dst(pl), rdst))

    cnt, rdst = mailbox.fold((state["count"], jnp.asarray(-1, I32)), apply)
    return ({"count": cnt},
            Emit.single(rdst, cnt, 1, 4, when=rdst >= 0))


def make_system(name):
    return ActorSystem.create(name, CFG)


def test_device_actor_tell_and_read():
    system = make_system("bridge-tell")
    try:
        ref = system.actor_of(device_props(counter), "c1")
        assert isinstance(ref, DeviceActorRef)
        assert ref.path.name == "c1"
        for x in (1.0, 2.0, 3.5):
            ref.tell((ADD, [x]))
        h = get_handle(system)
        h.step()
        assert ref.read_state("count") == 6.5
    finally:
        system.terminate()
        system.await_termination(10.0)


def test_device_ask_roundtrip():
    """ask completes via a promise row the behavior replies to — the
    device-resident PromiseActorRef."""
    system = make_system("bridge-ask")
    try:
        ref = system.actor_of(device_props(counter), "c2")
        ref.tell((ADD, [10.0]))
        ref.tell((ADD, [5.0]))
        # the auto-pump drives steps; no manual stepping
        reply = ask_sync(ref, (GET, [0.0]), timeout=10.0)
        assert reply[0] == 15.0
        # reply ids ride inbox.sum: the bridge pins the exact-per-segment
        # kernel itself (bridge.ASK_DELIVERY), on every platform
        assert get_handle(system).runtime._core.delivery == "scatter"
    finally:
        system.terminate()
        system.await_termination(10.0)


@pytest.mark.slow  # ~13 s: demoted to the slow tier (ISSUE 18 budget
# note) — multi-actor device emit volleys through the public API stay
# tier-1-covered by test_device_block_ring_public_api
def test_device_ping_pong_public_api():
    """BASELINE TellOnly/ping-pong shape through system.actor_of: two device
    actors exchanging a counter token."""

    @behavior("pp", {"hits": ((), F32), "peer": ((), I32)}, inbox="slots")
    def pp(state, mailbox: Mailbox, ctx):
        def apply(carry, t, pl):
            return carry + pl[0]

        got = mailbox.fold(jnp.asarray(0.0, F32), apply)
        any_msg = mailbox.count > 0
        return ({"hits": state["hits"] + got},
                Emit.single(state["peer"], jnp.asarray([1.0]), 1, 4,
                            when=any_msg))

    system = make_system("bridge-pp")
    try:
        a = system.actor_of(device_props(pp), "a")
        b = system.actor_of(
            device_props(pp, init_state={"peer": np.asarray([0], np.int32)}),
            "b")
        h = get_handle(system)
        # wire a -> b after spawn (rows are known now)
        h.runtime.state["peer"] = h.runtime.state["peer"].at[a.row].set(b.row)
        a.tell((0, [1.0]))     # serve
        h.step(20)             # 20 steps of volleys on device
        total = float(a.read_state("hits") + b.read_state("hits"))
        assert total >= 19.0   # one hop per step after the serve lands
    finally:
        system.terminate()
        system.await_termination(10.0)


def test_device_block_ring_public_api():
    """BASELINE ring config through the public API: one block ref, bulk
    seed, on-device volleys, no per-actor Python objects."""

    @behavior("ringb", {"received": ((), F32)}, inbox="slots")
    def ringb(state, mailbox: Mailbox, ctx):
        def apply(carry, t, pl):
            return carry + pl[0]

        got = mailbox.fold(jnp.asarray(0.0, F32), apply)
        nxt = (ctx.actor_id + 1) % jnp.asarray(256, I32)
        return ({"received": state["received"] + got},
                Emit.single(nxt, jnp.asarray([1.0]), 1, 4,
                            when=mailbox.count > 0))

    system = make_system("bridge-ring")
    try:
        block = system.actor_of(device_props(ringb, n=256), "ring")
        assert isinstance(block, DeviceBlockRef)
        assert len(block) == 256
        block.tell((0, [1.0]))  # one token to every actor (bulk staged)
        h = get_handle(system)
        h.step(10)
        # every executed step delivers exactly one token per actor; the
        # auto-pump may step at ANY point between these reads, so snapshot
        # the authoritative device step counter FIRST and lower-bound the
        # delivered total (reading received first raced a pump slipping in
        # between the two reads — observed once in a full-suite run)
        import jax
        steps_before = int(jax.device_get(h.runtime.step_count))
        received = block.read_state("received")
        assert steps_before >= 10
        assert received.sum() >= 256 * steps_before
        assert received.sum() % 256 == 0
        # single-row ref derived from the block works
        r0 = block[0]
        assert isinstance(r0, DeviceActorRef)
        assert r0.read_state("received") == received[0]
    finally:
        system.terminate()
        system.await_termination(10.0)


@pytest.mark.slow  # ~15 s: demoted to the slow tier (ISSUE 18 budget
# note) to pay for the evloop/columnar-admission tier-1 additions
def test_rebuild_on_new_behavior_preserves_state():
    """Spawning a new behavior type after the runtime is built re-traces the
    switch while keeping rows, state and pending messages."""
    system = make_system("bridge-rebuild")
    try:
        c = system.actor_of(device_props(counter), "c")
        c.tell((ADD, [7.0]))
        h = get_handle(system)
        h.step()
        assert c.read_state("count") == 7.0

        @behavior("other", {"seen": ((), F32)}, inbox="slots")
        def other(state, mailbox: Mailbox, ctx):
            def apply(carry, t, pl):
                return carry + pl[0]
            return ({"seen": state["seen"] +
                     mailbox.fold(jnp.asarray(0.0, F32), apply)},
                    Emit.none(1, 4))

        o = system.actor_of(device_props(other), "o")
        c.tell((ADD, [3.0]))
        o.tell((0, [2.0]))
        h.step()
        assert c.read_state("count") == 10.0  # old state survived rebuild
        assert o.read_state("seen") == 2.0
    finally:
        system.terminate()
        system.await_termination(10.0)


def test_device_ref_watch_and_stop_dead_letters():
    from akka_tpu.actor.messages import DeadLetter
    from akka_tpu.testkit import TestProbe
    system = make_system("bridge-watch")
    try:
        ref = system.actor_of(device_props(counter), "mortal")
        probe = TestProbe(system)
        probe.watch(ref)
        dl_probe = TestProbe(system)
        system.event_stream.subscribe(dl_probe.ref, DeadLetter)
        ref.stop()
        t = probe.expect_terminated(ref, 5.0)
        assert t.actor is ref
        ref.tell((ADD, [1.0]))  # late tell -> dead letters
        dl = dl_probe.receive_one(5.0)
        assert isinstance(dl, DeadLetter)
    finally:
        system.terminate()
        system.await_termination(10.0)


def test_default_dispatcher_tpu_batched():
    """The north star seam: akka.actor.default-dispatcher.type=tpu-batched —
    host actors still run (they share the dispatcher thread pool), device
    props land on the device, through the same public API."""
    cfg = {"akka": {"stdout-loglevel": "OFF", "log-dead-letters": 0,
                    "actor": {"default-dispatcher": {
                        "type": "tpu-batched",
                        "capacity": 1 << 10, "payload-width": 4,
                        "mailbox-slots": 4, "promise-rows": 16,
                        "host-inbox": 1024}}}}
    system = ActorSystem.create("bridge-default", cfg)
    try:
        # a plain host actor on the tpu-batched dispatcher's thread pool
        from akka_tpu import Props
        from akka_tpu.actor.actor import Actor
        from akka_tpu.testkit import TestProbe

        class Echo(Actor):
            def receive(self, message):
                self.sender.tell(("echo", message), self.self_ref)

        host = system.actor_of(Props(factory=Echo, cls=Echo), "host-echo")
        probe = TestProbe(system)
        host.tell("hi", probe.ref)
        assert probe.receive_one(5.0) == ("echo", "hi")

        # a device actor through the same default dispatcher
        dev = system.actor_of(device_props(counter), "dev-counter")
        dev.tell((ADD, [4.0]))
        assert ask_sync(dev, (GET, [0.0]), timeout=10.0)[0] == 4.0
        blk = system.actor_of(device_props(counter, n=4), "dev-block")
        assert len(blk) == 4
    finally:
        system.terminate()
        # device children tell the guardian they stopped, like any child
        assert system.await_termination(10.0)


def test_ask_reply_id_dtype_validated_at_build():
    """VERDICT r3 #6: the ask reply-to row id is a value cast into the
    payload dtype's last column; a capacity whose ids cannot roundtrip
    must fail FAST at handle construction, not corrupt routing silently
    (AskSupport.scala:476 — PromiseActorRef identity is never lossy)."""
    import jax.numpy as jnp
    import pytest
    from akka_tpu.batched.bridge import BatchedRuntimeHandle, max_exact_row_id

    # float32: 2^24 ids are exact -> 1M rows fine
    BatchedRuntimeHandle(capacity=1 << 20, payload_dtype=jnp.float32)
    # bfloat16: only 2^8 ids are exact -> 1M rows must be refused
    with pytest.raises(ValueError, match="bfloat16"):
        BatchedRuntimeHandle(capacity=1 << 20, payload_dtype=jnp.bfloat16)
    # ...but a system small enough for bf16 ids builds
    BatchedRuntimeHandle(capacity=256, payload_dtype=jnp.bfloat16,
                         promise_rows=8)
    # float16: 2^11
    with pytest.raises(ValueError, match="float16"):
        BatchedRuntimeHandle(capacity=1 << 12, payload_dtype=jnp.float16)
    assert max_exact_row_id(jnp.float32) == 1 << 24
    assert max_exact_row_id(jnp.bfloat16) == 1 << 8
    assert max_exact_row_id(jnp.int32) == (1 << 31) - 1


def test_bf16_small_system_ask_roundtrip():
    """A bf16 payload system within the exact-id range must WORK end to
    end: ask routes the reply through the value-cast id correctly."""
    import jax.numpy as jnp
    from akka_tpu.batched import Emit, behavior
    from akka_tpu.batched.bridge import BatchedRuntimeHandle, reply_dst

    P = 4

    @behavior("bf16-echo", {})
    def echo(state, inbox, ctx):
        return (state, Emit.single(
            reply_dst(inbox.sum), inbox.sum * 2, 1, P,
            when=inbox.count > 0))

    h = BatchedRuntimeHandle(capacity=128, payload_width=P,
                             payload_dtype=jnp.bfloat16, promise_rows=8,
                             host_inbox=32)
    try:
        rows = h.spawn(echo, 1)
        fut = h.ask(int(rows[0]), (0, [3.0, 0, 0, 0]), timeout=30.0)
        reply = fut.result(40.0)
        assert float(reply[0]) == 6.0
    finally:
        h.shutdown()


# ------------------------------------------------- depth-k pipeline seams
def test_ask_timeout_with_pipeline_in_flight():
    """An ask that times out while the depth-4 pump keeps k programs in
    flight must fail with AskTimeoutException (host deadline sweep runs
    off the attention word, no wide readback needed), quarantine the
    promise row as a zombie, and leave the handle healthy: a later ask
    against a newly spawned behavior (forcing a rebuild on top of the
    zombie) still completes."""
    from akka_tpu.batched import Emit, behavior
    from akka_tpu.batched.bridge import BatchedRuntimeHandle, reply_dst
    from akka_tpu.pattern.ask import AskTimeoutException

    @behavior("mute", {})
    def mute(state, inbox, ctx):
        return state, Emit.none(1, 4)

    @behavior("echo2", {})
    def echo2(state, inbox, ctx):
        return state, Emit.single(reply_dst(inbox.sum), inbox.sum * 2, 1, 4,
                                  when=inbox.count > 0)

    h = BatchedRuntimeHandle(capacity=128, payload_width=4, promise_rows=8,
                             host_inbox=32, pipeline_depth=4)
    try:
        rows = h.spawn(mute, 1)
        fut = h.ask(int(rows[0]), (0, [1.0]), timeout=0.25)
        with pytest.raises(AskTimeoutException):
            fut.result(20.0)
        assert h._promise_zombies  # row quarantined, not recycled yet
        assert h.pipeline_stats()["steps"] > 0

        erow = h.spawn(echo2, 1)  # rebuild with the zombie outstanding
        reply = h.ask_sync(int(erow[0]), (0, [21.0]), timeout=30.0)
        assert float(reply[0]) == 42.0
    finally:
        h.shutdown()


def test_pipelined_steps_with_an_unresolved_waiter():
    """The depth-4 pump with an ask outstanding that nobody answers: the
    stepping driver keeps enqueueing and draining attention words, the
    waiter stays unresolved, and pipeline_stats records the depth and
    the programs it ran."""
    from akka_tpu.batched import Emit, behavior
    from akka_tpu.batched.bridge import BatchedRuntimeHandle

    @behavior("mute-waiter", {})
    def mute(state, inbox, ctx):
        return state, Emit.none(1, 4)

    h = BatchedRuntimeHandle(capacity=128, payload_width=4, promise_rows=8,
                             host_inbox=32, pipeline_depth=4)
    try:
        row = int(h.spawn(mute, 1)[0])
        fut = h.ask(row, (0, [1.0]), timeout=600.0)
        before = h.pipeline_stats()["steps"]
        h.step(16, depth=4)
        stats = h.pipeline_stats()
        assert stats["depth"] == 4
        assert stats["steps"] >= before + 16
        assert stats["drains"] > 0
        assert not fut.done()
    finally:
        h.shutdown()


def test_rebuild_races_full_pipeline():
    """spawn() of a new behavior (=> _rebuild_locked) racing a stepper
    thread that keeps the depth-4 pipeline full: no exceptions on either
    side, always-on rows keep advancing in lockstep, and a tell to the
    freshly spawned behavior lands exactly once."""
    import threading
    import time

    from akka_tpu.batched import Emit, behavior
    from akka_tpu.batched.bridge import BatchedRuntimeHandle

    @behavior("race-acc", {"acc": ((), F32)}, always_on=True)
    def race_acc(state, inbox, ctx):
        return {"acc": state["acc"] + 1.0}, Emit.none(1, 4)

    @behavior("race-late", {"seen": ((), F32)})
    def race_late(state, inbox, ctx):
        return ({"seen": state["seen"] + inbox.sum[0]}, Emit.none(1, 4))

    h = BatchedRuntimeHandle(capacity=128, payload_width=4, promise_rows=8,
                             host_inbox=64, pipeline_depth=4)
    errors = []
    try:
        rows = h.spawn(race_acc, 16)
        stop = threading.Event()

        def stepper():
            try:
                while not stop.is_set():
                    h.step(8)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        t = threading.Thread(target=stepper)
        t.start()
        try:
            time.sleep(0.05)  # pipeline warm and full
            lrow = h.spawn(race_late, 1)   # rebuild mid-flight
            h.tell(int(lrow[0]), (0, [5.0]))
            time.sleep(0.05)
        finally:
            stop.set()
            t.join(60.0)
        assert not t.is_alive()
        assert not errors, errors
        h.step(2)  # make sure the tell's flush has executed
        acc = np.asarray(h.read_state("acc", rows))
        assert np.unique(acc).size == 1  # lanes advanced in lockstep
        assert acc[0] >= 8.0             # ...through rebuild, not reset
        assert float(h.read_state("seen", lrow)[0]) == 5.0
    finally:
        h.shutdown()


def _chaos_parity_run(depth, backend, seed, rate, n, windows):
    """One handle lifecycle: always-on chaos accumulator + staged tells,
    driven ONLY via h.step() windows (tells go through runtime.tell so
    the background pump stays dormant and the step count is exact)."""
    import jax

    from akka_tpu.actor.supervision import Directive
    from akka_tpu.batched import Emit, LaneSupervisor, behavior
    from akka_tpu.batched.bridge import BatchedRuntimeHandle
    from akka_tpu.testkit import chaos

    @behavior("par-acc", {"acc": ((), F32)}, always_on=True,
              supervisor=LaneSupervisor(directive=Directive.RESUME))
    def par_acc(state, inbox, ctx):
        return {"acc": state["acc"] + 1.0 + inbox.sum[0]}, Emit.none(1, 4)

    b = chaos.inject(par_acc, seed=seed, crash_rate=rate)
    h = BatchedRuntimeHandle(capacity=128, payload_width=4, promise_rows=8,
                             host_inbox=64, pipeline_depth=depth,
                             delivery_backend=backend)
    try:
        rows = h.spawn(b, n)
        base = int(rows[0])
        msg = 0
        for w in windows:
            # deterministic tell schedule exercising the delivery backend
            for _ in range(3):
                h.runtime.tell(base + (msg % n), [float(msg + 1), 0, 0, 0])
                msg += 1
            h.step(w)
        rt = h.runtime
        state = {k: np.asarray(jax.device_get(v))
                 for k, v in sorted(rt.state.items())}
        counts = dict(rt.supervision_counts)
        steps = int(jax.device_get(rt.step_count))
        return np.asarray(rows), state, counts, steps
    finally:
        h.shutdown()


@pytest.mark.parametrize("backend", ["xla", "reference"])
def test_depth_k_bit_parity_with_chaos_oracle(backend):
    """Depth-1 (synchronous pump) and depth-4 (pipelined) runs of the
    same chaos schedule must be BIT-identical: every state column, the
    supervision counters and the step count. The failed counter is also
    checked against the numpy chaos oracle — pipelining may not change
    what executes, only when the host looks at it."""
    from akka_tpu.testkit import chaos

    seed, rate, n = 11, 0.08, 48
    windows = (7, 5, 9)
    rows1, s1, c1, n1 = _chaos_parity_run(1, backend, seed, rate, n, windows)
    rows4, s4, c4, n4 = _chaos_parity_run(4, backend, seed, rate, n, windows)

    assert n1 == n4 == sum(windows)
    np.testing.assert_array_equal(rows1, rows4)
    assert s1.keys() == s4.keys()
    for col in s1:
        np.testing.assert_array_equal(s1[col], s4[col], err_msg=col)
    assert c1 == c4
    # oracle: always-on lanes receive every step; RESUME handles each hit
    lanes = rows1
    expect_failed = int(sum(
        chaos.chaos_hit_np(seed, s, lanes, rate, chaos.CRASH_SALT).sum()
        for s in range(sum(windows))))
    assert c1["failed"] == expect_failed > 0
    assert c1["resumed"] == expect_failed
