"""The host side of the device plane, measured from inside the program
(ISSUE 36): the one span log behind `trace_span`, its join with a profiler
trace, the compile log, and the spans, counters and events both step
drivers leave through the one bracket of batched/host_side.py."""

import glob
import threading

import jax
import jax.numpy as jnp
import pytest

from akka_tpu.batched import BatchedSystem, Emit, behavior
from akka_tpu.batched.host_side import HostSide
from akka_tpu.event import flight_recorder as fr
from akka_tpu.event.flight_recorder import (InMemoryFlightRecorder,
                                            clear_span_log, compile_log,
                                            span_log, trace_span)
from akka_tpu.models.baseline_benches import (build_cross_shard, build_ring,
                                              seed_ring_full)

RUN = "akka.device.run["


def small(which: str):
    """A small seeded ring on one device, or a mesh ring over four virtual
    devices; fresh, so its step programs compile in the test that runs it."""
    if which == "batched":
        s = build_ring(n=256, static=False, delivery="auto")
    else:
        s = build_cross_shard(4, 64, n_devices=4)
    seed_ring_full(s)
    return s


def own_log():
    """This thread's records: a worker that ran a pumped handle before may
    still have a thread of it appending to the process's one log."""
    me = threading.get_ident()
    return [r for r in span_log() if r["thread"] == me]


def runs(records):
    return [r for r in records if r["name"].startswith(RUN)]


# ------------------------------------------------------------ the span log
def test_ring_keeps_at_most_its_capacity_and_the_newest():
    clear_span_log()
    n = fr.SPAN_LOG_CAPACITY + 37
    for i in range(n):
        with trace_span("akka.test.fill", i=i):
            pass
    log = span_log()
    assert len(log) == fr.SPAN_LOG_CAPACITY
    assert [r["stats"]["i"] for r in log] == list(range(37, n))
    ids = [r["id"] for r in log]
    assert ids == sorted(ids) and len(set(ids)) == len(ids)
    clear_span_log()
    assert span_log() == []


def test_record_has_name_clock_id_parent_and_stats():
    clear_span_log()
    with trace_span("akka.test.outer", a=1) as outer:
        with trace_span("akka.test.inner") as inner:
            inner.stats["late"] = 7  # known only inside: the log has it
    inner_rec, outer_rec = own_log()  # a parent ends after its child
    assert outer_rec["name"] == "akka.test.outer"
    assert outer_rec["parent"] == 0 and outer_rec["stats"] == {"a": 1}
    assert inner_rec["parent"] == outer_rec["id"] == outer.id
    assert inner_rec["stats"] == {"late": 7}
    assert outer_rec["t0_ns"] <= inner_rec["t0_ns"] <= inner_rec["t1_ns"] \
        <= outer_rec["t1_ns"]
    assert outer.seconds == (outer_rec["t1_ns"] - outer_rec["t0_ns"]) / 1e9
    assert outer_rec["thread"] == threading.get_ident()


def test_parents_nest_within_a_thread_and_never_across_threads():
    clear_span_log()
    gate = threading.Barrier(4, timeout=30)

    def work(k):
        with trace_span("akka.test.root", k=k):
            gate.wait()  # all four roots are open at once
            for j in range(50):
                with trace_span("akka.test.child", k=k, j=j):
                    with trace_span("akka.test.leaf", k=k):
                        pass

    threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    log = span_log()
    by_id = {r["id"]: r for r in log}
    assert len(log) == 4 * (1 + 50 * 2)
    for r in log:
        if r["name"] == "akka.test.root":
            assert r["parent"] == 0
            continue
        parent = by_id[r["parent"]]
        assert parent["thread"] == r["thread"]
        assert parent["stats"]["k"] == r["stats"]["k"]
        assert parent["name"] == ("akka.test.root"
                                  if r["name"] == "akka.test.child"
                                  else "akka.test.child")


def test_span_survives_an_exception_and_unwinds_the_stack():
    clear_span_log()
    with pytest.raises(KeyError):
        with trace_span("akka.test.raises"):
            raise KeyError("boom")
    with trace_span("akka.test.after"):
        pass
    first, second = own_log()
    assert first["name"] == "akka.test.raises"
    assert second["parent"] == 0  # the failed span is no one's parent


def test_the_profiler_entry_points_nobody_called_are_gone():
    assert not hasattr(fr, "start_trace") and not hasattr(fr, "stop_trace")


# ---------------------------------------------- the log on the trace's clock
def _traced(tmp_path, system, chunks):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(chunks):
            system.run(2)
            system.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    return path


@pytest.mark.parametrize("which", ["batched", "sharded"])
def test_every_dispatch_of_the_log_is_in_the_trace_by_its_id(which, tmp_path):
    system = small(which)
    system.run(2)  # warm: nothing compiles under the profiler
    system.block_until_ready()
    clear_span_log()
    path = _traced(tmp_path, system, chunks=12)
    starts = fr.trace_span_starts(path)
    offset = fr.trace_clock_offset_ns(starts)
    assert offset is not None
    mine = runs(own_log())
    assert len(mine) == 12
    for r in mine:
        assert r["id"] in starts
        # the log's clock plus the offset IS the trace's clock, to 100 us
        assert abs(r["t0_ns"] + offset - starts[r["id"]]) < 100_000
        assert fr.to_trace_ns(r["t0_ns"] / 1e9, offset) == \
            pytest.approx(starts[r["id"]], abs=100_000)
    waits = [r for r in own_log() if r["name"] == "akka.device.wait"]
    assert len(waits) == 12 and all(r["id"] in starts for r in waits)
    # the annotation carries the id beside the stats it always had
    data = jax.profiler.ProfileData.from_file(path)
    events = [e for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name == "akka.device.run[2]"]
    stats = dict(events[0].stats)
    assert {"span_id", "step0", "steps", "starved"} <= set(stats)
    assert stats["steps"] == 2 and stats["span_id"] in {r["id"] for r in mine}


def test_no_joined_span_gives_no_offset():
    clear_span_log()
    assert fr.trace_clock_offset_ns({123: 5}) is None


# ---------------------------------------------------------- the compile log
@pytest.mark.parametrize("which,program", [("batched", "jit(_run_impl)"),
                                           ("sharded", "jit(multi_step)")])
def test_a_new_n_is_a_new_program_and_shows_as_one(which, program):
    system = small(which)
    system.block_until_ready()
    clear_span_log()
    system.run(3)
    system.run(5)
    system.run(3)
    system.block_until_ready()
    dispatched = runs(own_log())
    assert [r["stats"]["steps"] for r in dispatched] == [3, 5, 3]
    assert [r["stats"]["step0"] for r in dispatched] == [0, 3, 8]
    compiled = [r["stats"].get("compiled", 0) for r in dispatched]
    assert compiled[0] > 0 and compiled[1] > 0 and compiled[2] == 0
    rows = [r for r in compile_log() if r["program"] == program]
    assert [r["steps"] for r in rows] == [3, 5]
    assert [r["span"] for r in rows] == ["akka.device.run[3]",
                                         "akka.device.run[5]"]
    for r in rows:
        assert r["trace_s"] > 0 and r["lower_s"] > 0 and r["compile_s"] > 0
        assert r["cache"] in (None, "hit", "miss")
    stats = system.host_stats()
    assert stats["dispatches"] == 3
    assert stats["compiles"] == compiled[0] + compiled[1]
    assert stats["compile_s"] >= sum(r["compile_s"] for r in rows)
    assert stats["last_compile"] == {"program": program, "step": 3}
    assert 0 < stats["dispatch_us_p50"] <= stats["dispatch_us_p99"]


def test_compile_events_are_span_shaped_records_under_their_dispatch():
    system = small("batched")
    clear_span_log()
    system.run(4)
    system.block_until_ready()
    log = own_log()
    (run,) = runs(log)
    for event in (fr.TRACE_EVENT, fr.LOWER_EVENT, fr.COMPILE_EVENT):
        mine = [r for r in log if r["name"] == event
                and r["parent"] == run["id"]]
        assert mine, event
        for r in mine:
            assert r["t1_ns"] - r["t0_ns"] == int(r["stats"]["secs"] * 1e9)
            assert run["t0_ns"] <= r["t1_ns"] <= run["t1_ns"]
    (backend,) = [r for r in log if r["name"] == fr.COMPILE_EVENT
                  and r["stats"].get("fun_name") == "jit(_run_impl)"]
    assert backend["stats"]["steps"] == 4
    assert backend["stats"]["span"] == "akka.device.run[4]"


def test_listening_twice_registers_once():
    import jax.monitoring  # noqa: F401
    from jax._src import monitoring

    fr.listen_for_compiles()
    before = len(monitoring.get_event_duration_listeners())
    fr.listen_for_compiles()
    HostSide("batched")
    assert len(monitoring.get_event_duration_listeners()) == before


# ------------------------------------------------ spans where the host works
@pytest.mark.parametrize("which,devices", [("batched", 1), ("sharded", 4)])
def test_build_span_holds_the_constructor_and_names_what_was_built(
        which, devices):
    clear_span_log()
    system = small(which)
    (build,) = [r for r in own_log() if r["name"] == "akka.setup.build"]
    assert build["parent"] == 0
    assert build["stats"]["actors"] == system.capacity
    assert build["stats"]["inbox_rows"] == system.inbox_dst.shape[0]
    assert build["stats"]["devices"] == devices
    assert build["t1_ns"] > build["t0_ns"]


@pytest.mark.parametrize("which", ["batched", "sharded"])
def test_starved_is_true_after_a_wait_and_host_stats_counts_it(which):
    system = small(which)
    system.run(2)
    system.block_until_ready()
    clear_span_log()
    before = system.host_stats()["starved"]
    system.run(2)  # the device had nothing queued: the host made it wait
    system.block_until_ready()
    (run,) = runs(own_log())
    assert run["stats"]["starved"] == 1
    assert system.host_stats()["starved"] == before + 1
    assert system.host_stats()["dispatches"] == 2


@pytest.mark.parametrize("which", ["batched", "sharded"])
def test_waits_and_reads_that_sync_leave_their_spans(which):
    system = small(which)
    system.run(1)
    clear_span_log()
    system.block_until_ready()
    system.read_state("received")
    names = [r["name"] for r in own_log()]
    assert names == ["akka.device.wait", "akka.device.wait",
                     "akka.device.read[state]"]
    read = own_log()[-1]
    assert own_log()[1]["parent"] == read["id"]  # the read's own wait


def test_spill_router_and_metric_reads_are_named_for_what_they_read():
    from akka_tpu.models.baseline_benches import build_bank, build_router_pool
    bank = build_bank(n_tellers=64, n_accounts=16, mailbox_slots=2,
                      spill_capacity=64)
    pool = build_router_pool(n_producers=32, n_routees=8)
    clear_span_log()
    bank.read_spill()
    pool.read_routers()
    names = {r["name"] for r in own_log()}
    assert {"akka.device.read[spill]", "akka.device.read[routers]",
            "akka.device.read[state]", "akka.device.wait"} <= names


def test_metric_drain_is_a_read_span_only_where_the_slab_is_on():
    @behavior("c", {"n": ((), jnp.int32)})
    def counter(state, inbox, ctx):
        return ({"n": state["n"] + inbox.count}, Emit.none(1, 4))

    off = BatchedSystem(capacity=8, behaviors=[counter], host_inbox=8)
    on = BatchedSystem(capacity=8, behaviors=[counter], host_inbox=8,
                       metrics_enabled=True)
    clear_span_log()
    assert off.drain_metrics() is None
    assert own_log() == []
    on.drain_metrics()
    assert [r["name"] for r in own_log()] == ["akka.device.read[metrics]"]


def test_warmup_is_one_span_and_one_device_compile_event():
    @behavior("c", {"n": ((), jnp.int32)})
    def counter(state, inbox, ctx):
        return ({"n": state["n"] + inbox.count}, Emit.none(1, 4))

    rec = InMemoryFlightRecorder()
    s = BatchedSystem(capacity=8, behaviors=[counter], host_inbox=8)
    s.flight_recorder = rec
    clear_span_log()
    s.warmup()
    (warm,) = [r for r in own_log() if r["name"] == "akka.setup.warmup"]
    assert warm["stats"]["compiled"] >= 3  # step, flush, flush + step
    (event,) = rec.of_type("device_compile")
    assert event["system"] == "batched"
    assert event["elapsed_s"] == (warm["t1_ns"] - warm["t0_ns"]) / 1e9
    assert s.host_stats()["compiles"] == warm["stats"]["compiled"]
    assert s.host_stats()["dispatches"] == 0


@pytest.mark.parametrize("which", ["batched", "sharded"])
def test_flight_recorder_takes_its_seconds_from_the_span(which):
    rec = InMemoryFlightRecorder()
    system = small(which)
    system.flight_recorder = rec
    clear_span_log()
    system.run(3)
    system.run(3)
    system.run(6)
    system.block_until_ready()
    spans = runs(own_log())
    steps = rec.of_type("device_step")
    assert [e["system"] for e in steps] == [which] * 3
    assert [e["n_steps"] for e in steps] == [3, 3, 6]
    assert [e["elapsed_s"] for e in steps] == \
        [(r["t1_ns"] - r["t0_ns"]) / 1e9 for r in spans]
    # a run(n) with a new n is a new program: the flight shows its compile
    compiles = rec.of_type("device_compile")
    assert [e["elapsed_s"] for e in compiles] == \
        [steps[0]["elapsed_s"], steps[2]["elapsed_s"]]


def test_single_step_dispatch_is_the_same_bracket():
    @behavior("c", {"n": ((), jnp.int32)})
    def counter(state, inbox, ctx):
        return ({"n": state["n"] + inbox.count}, Emit.none(1, 4))

    rec = InMemoryFlightRecorder()
    s = BatchedSystem(capacity=8, behaviors=[counter], host_inbox=8)
    s.flight_recorder = rec
    s.spawn_block(counter, 8)
    clear_span_log()
    s.tell(0, [1.0, 0, 0, 0])
    s.step()
    s.step()
    s.block_until_ready()
    first, second = [r for r in own_log() if r["name"] == "akka.device.step"]
    assert first["stats"]["step0"] == 0 and second["stats"]["step0"] == 1
    assert first["stats"]["steps"] == 1 and "starved" in first["stats"]
    assert first["stats"]["compiled"] > 0  # the fused flush + step program
    assert [e["event"] for e in rec.events()
            if e["event"].startswith("device_")][:2] == \
        ["device_flush", "device_step"]
    assert s.host_stats()["dispatches"] == 2


# ------------------------------------------------------- the operator's view
def test_host_stats_percentiles_are_nearest_rank():
    host = HostSide("batched")
    assert host.host_stats() == {
        "dispatches": 0, "dispatch_us_p50": 0.0, "dispatch_us_p99": 0.0,
        "starved": 0, "compiles": 0, "compile_s": 0.0, "last_compile": None}
    host._dispatch_ns.extend([1_000, 100_000])
    host.dispatches = 2
    stats = host.host_stats()
    assert stats["dispatch_us_p50"] == 1.0  # of [a, b] the median is a
    assert stats["dispatch_us_p99"] == 100.0


def test_bridge_registers_the_device_host_collector():
    from akka_tpu.batched.bridge import BatchedRuntimeHandle
    from akka_tpu.event.metrics import MetricsRegistry

    reg = MetricsRegistry()
    h = BatchedRuntimeHandle(capacity=64, payload_width=4, host_inbox=64,
                             promise_rows=8, metrics_registry=reg)
    try:
        assert h.host_stats() == {}  # no runtime built yet
        h.step(3)
        stats = h.host_stats()
        assert stats["dispatches"] == 3 and stats["compiles"] > 0
        pulled = dict(reg._pull_collectors())
        assert pulled["device_host_dispatches"] == 3.0
        assert pulled["device_host_starved"] == float(stats["starved"])
        assert pulled["device_host_compiles"] == float(stats["compiles"])
        assert "device_host_last_compile" not in pulled  # not a number
        assert pulled["pipeline_dispatch_p50_us"] == stats["dispatch_us_p50"]
    finally:
        h.shutdown()
