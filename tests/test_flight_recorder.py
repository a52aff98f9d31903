"""Flight recorder SPI: lifecycle/remoting/device events behind the
noop-default seam (JFRActorFlightRecorder selection parity — SURVEY.md §5
tracing; reference hook points ArteryTransport.scala:344,436-466)."""

import json
import os

from akka_tpu import Actor, ActorSystem, Props
from akka_tpu.event.flight_recorder import (InMemoryFlightRecorder,
                                            JsonlFlightRecorder,
                                            NoOpFlightRecorder, from_config,
                                            trace_span)


class Boomer(Actor):
    def receive(self, msg):
        if msg == "boom":
            raise RuntimeError("kapow")


def test_noop_is_default_and_inert():
    system = ActorSystem("fr-default")
    try:
        assert isinstance(system.flight_recorder, NoOpFlightRecorder)
        assert system.flight_recorder.events() == []
    finally:
        system.terminate()
        system.await_termination(10)


def test_memory_recorder_sees_lifecycle():
    system = ActorSystem("fr-mem", {
        "akka": {"flight-recorder": {"implementation": "memory"}}})
    try:
        fr = system.flight_recorder
        assert isinstance(fr, InMemoryFlightRecorder)
        ref = system.actor_of(Props.create(Boomer), "boomer")
        import time

        def spawned_boomer():
            return any(e["path"].endswith("/user/boomer")
                       for e in fr.of_type("actor_spawned"))

        deadline = time.time() + 5
        while time.time() < deadline and not spawned_boomer():
            time.sleep(0.01)
        assert spawned_boomer()

        ref.tell("boom")  # supervised restart
        deadline = time.time() + 5
        while time.time() < deadline and not fr.of_type("actor_restarted"):
            time.sleep(0.01)
        assert fr.of_type("actor_failed")
        assert fr.of_type("actor_restarted")

        ref.stop()
        deadline = time.time() + 5
        while time.time() < deadline and not any(
                e["path"].endswith("/user/boomer")
                for e in fr.of_type("actor_stopped")):
            time.sleep(0.01)
        assert any(e["path"].endswith("/user/boomer")
                   for e in fr.of_type("actor_stopped"))
    finally:
        system.terminate()
        system.await_termination(10)


def test_jsonl_recorder_writes_lines(tmp_path):
    path = str(tmp_path / "flight.jsonl")
    system = ActorSystem("fr-jsonl", {
        "akka": {"flight-recorder": {"implementation": "jsonl",
                                     "path": path}}})
    try:
        assert isinstance(system.flight_recorder, JsonlFlightRecorder)
        system.actor_of(Props.create(Boomer), "b")
        import time
        deadline = time.time() + 5
        while time.time() < deadline and not os.path.getsize(path):
            time.sleep(0.01)
    finally:
        system.terminate()
        system.await_termination(10)
    with open(path) as fh:
        events = [json.loads(line) for line in fh if line.strip()]
    assert any(e["event"] == "actor_spawned" for e in events)
    for e in events:
        assert "ts" in e


def test_device_runtime_records_steps():
    import jax.numpy as jnp
    from akka_tpu.batched import BatchedSystem, Emit, behavior

    @behavior("c", {"n": ((), jnp.int32)})
    def counter(state, inbox, ctx):
        return ({"n": state["n"] + inbox.count}, Emit.none(1, 4))

    fr = InMemoryFlightRecorder()
    s = BatchedSystem(capacity=8, behaviors=[counter], payload_width=4,
                      host_inbox=8)
    s.flight_recorder = fr
    s.spawn_block(counter, 8)
    s.tell(0, [1.0, 0, 0, 0])
    s.step()
    s.run(3)
    s.block_until_ready()
    steps = fr.of_type("device_step")
    assert len(steps) == 2
    assert steps[1]["n_steps"] == 3
    assert fr.of_type("device_flush")[0]["staged"] == 1


def test_remote_events_recorded():
    base = {"akka": {"actor": {"provider": "remote"},
                     "remote": {"transport": "inproc"},
                     "flight-recorder": {"implementation": "memory"}}}
    a = ActorSystem("fra", base)
    b = ActorSystem("frb", base)
    try:
        class Echo(Actor):
            def receive(self, msg):
                self.sender.tell(("ok", msg), self.self_ref)

        b.actor_of(Props.create(Echo), "echo")
        addr = b.address
        from akka_tpu.pattern.ask import ask_sync
        remote = a.actor_selection(
            f"akka://{b.name}@{addr.host}:{addr.port}/user/echo")
        assert ask_sync(remote, "hello", timeout=5.0) == ("ok", "hello")
        fra = a.flight_recorder
        assert fra.of_type("transport_started")
        assert fra.of_type("association_opened")
        assert fra.of_type("remote_message_sent")
        assert b.flight_recorder.of_type("remote_message_received")
    finally:
        a.terminate()
        b.terminate()
        a.await_termination(10)
        b.await_termination(10)


def test_trace_span_no_profiler_is_harmless():
    with trace_span("akka.test"):
        x = 1 + 1
    assert x == 2


def test_from_config_fallbacks():
    assert isinstance(from_config(None), NoOpFlightRecorder)


def test_rows_carry_dual_timestamps():
    """ISSUE 12 satellite: every recorded row gets wall `ts` AND
    monotonic `ts_mono` so tools/trace_export.py aligns FR rows with
    tracing spans without guessing a clock offset. Old single-timestamp
    rows (pre-satellite JSONL files) still parse — the converter treats
    `ts_mono` as optional."""
    import time

    fr = InMemoryFlightRecorder()
    fr.device_step("sys", 4, 0.01)
    fr.event("custom", answer=42)
    for ev in fr.events():
        assert 0 < ev["ts_mono"] <= time.monotonic()
        assert abs(ev["ts"] - time.time()) < 60.0
    step = fr.of_type("device_step")[0]
    assert (step["system"], step["n_steps"]) == ("sys", 4)
    assert fr.of_type("custom")[0]["answer"] == 42
    # a legacy wall-only row still flows through the converter
    import sys as _sys
    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                     "tools"))
    import trace_export
    doc = trace_export.to_perfetto([], [{"event": "old_row", "ts": 123.0}])
    assert trace_export.validate_trace(doc) == []


def test_profiler_import_is_cached_per_process():
    """ISSUE 12 satellite: `trace_span.__enter__` resolves jax.profiler
    through the module-level cache — ONE import per process, not one
    sys.modules round per span bracket."""
    from akka_tpu.event import flight_recorder as fr_mod
    with trace_span("akka.cache-check"):
        pass
    assert fr_mod._PROFILER is not None
    first = fr_mod._profiler()
    with trace_span("akka.cache-check-2"):
        pass
    assert fr_mod._profiler() is first
