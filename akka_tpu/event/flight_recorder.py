"""Flight recorder: structured runtime tracing behind a noop-by-default SPI.

Reference parity: the JDK Flight Recorder emitters selected at runtime —
typed actor events (akka-actor-typed/src/main/scala-jdk-9/akka/actor/typed/
internal/jfr/JFRActorFlightRecorder.scala, noop fallback
typed/internal/ActorFlightRecorder.scala) and remoting events
(akka-remote/src/main/scala-jdk-9/akka/remote/artery/jfr/Events.scala), with
hook points through ArteryTransport.start (ArteryTransport.scala:344,436-466).

The TPU translation (SURVEY.md §2.10 item 9): the host control plane emits
structured events into a pluggable recorder (noop / in-memory ring / JSONL
file), and the host side of the device plane is measured by ONE primitive,
`trace_span`. `with trace_span("akka.device.run[16]", step0=0, steps=16)`
does two things: while a jax.profiler session is open it is a
`TraceAnnotation` in that trace, beside the XLA ops the bracket launched;
and always, profiler or none, it appends one record to the process's span
log, a bounded in-memory ring (`span_log()`, `clear_span_log()`): name,
start and end in `time.monotonic_ns()`, a running id, the id of the
enclosing span of the same thread, the stats. The id rides into the
annotation as the stat `span_id`, so a span that is in both joins them
exactly: `trace_clock_offset_ns()` is the median difference of the joined
starts and `to_trace_ns()` puts any `time.monotonic()` instant (a tracing
span's `t0` / `t1`, a flight event's `ts_mono`) onto the trace's clock.
JAX's own monitoring events (trace, lower, backend compile, the persistent
cache's hits, misses and retrieval) land in the same ring as span-shaped
records once `listen_for_compiles()` ran; `compile_log()` folds them into
one row a compiled program. docs/OBSERVABILITY.md section 7 has the names.

Selection mirrors the reference's runtime pick: config
`akka.flight-recorder.implementation = noop|memory|jsonl` read at system
bootstrap; `noop` costs one no-inlined method call per hook, nothing else.
"""

from __future__ import annotations

import inspect
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple


class FlightRecorder:
    """SPI. Every hook is fire-and-forget and must never raise into the
    caller; implementations are thread-safe. Callers building non-trivial
    hook arguments (path strings, reprs) should gate on `enabled` so the
    noop configuration pays one attribute read, nothing else."""

    enabled = True

    # -- actor lifecycle (JFRActorFlightRecorder parity) ---------------------
    def actor_spawned(self, path: str) -> None: ...
    def actor_stopped(self, path: str) -> None: ...
    def actor_failed(self, path: str, cause: str) -> None: ...
    def actor_restarted(self, path: str, cause: str) -> None: ...

    # -- remoting (artery/jfr/Events.scala parity) ---------------------------
    def transport_started(self, address: str) -> None: ...
    def association_opened(self, peer: str) -> None: ...
    def association_quarantined(self, peer: str, reason: str) -> None: ...
    def remote_message_sent(self, peer: str, size: int) -> None: ...
    def remote_message_received(self, peer: str, size: int) -> None: ...

    # -- device runtime (no reference analogue; the TPU data plane) ----------
    def device_step(self, system: str, n_steps: int, elapsed_s: float) -> None: ...
    def device_flush(self, system: str, staged: int) -> None: ...
    def device_compile(self, system: str, elapsed_s: float) -> None: ...

    # in-graph supervision counter DELTA since the previous report
    # (batched/supervision.py COUNTER_NAMES): one event per step window,
    # emitted only when something happened — the watchdog's artifact shows
    # directive traffic without per-step device syncs
    def device_supervision(self, system: str, steps: int, failed: int,
                           resumed: int, restarted: int, stopped: int,
                           escalated: int, dead_letters: int) -> None: ...

    # depth-k dispatch pipeline counter DELTA since the previous report
    # (batched/bridge.py): programs enqueued/drained in the window and how
    # many drains paid the wide promise readback (wide_resolves) vs
    # host-only deadline checks — emitted at the pump's busy->idle edge
    # and at handle shutdown
    def device_pipeline(self, system: str, depth: int, steps: int,
                        drains: int, wide_resolves: int,
                        host_checks: int) -> None: ...

    # checkpoint/recovery (batched runtime + persistence/tell_journal):
    # one device_checkpoint per snapshot taken; checkpoint_failed when
    # snapshot IO degrades (the step loop keeps running); journal_truncated
    # when a torn record-log tail is repaired on open
    def device_checkpoint(self, system: str, step: int, elapsed_s: float,
                          size_bytes: int, path: str) -> None: ...

    def checkpoint_failed(self, system: str, error: str,
                          consecutive: int) -> None: ...

    def journal_truncated(self, path: str, dropped_bytes: int) -> None: ...

    # failure detection / degraded-mesh failover (batched/sentinel.py):
    # device_suspected when a shard's heartbeat lane trips its detector
    # (phi-accrual on frozen progress, or the wall-clock drain deadline);
    # device_evicted once the sentinel quarantines it; failover_completed
    # after the surviving-mesh rebuild resumes stepping (mttr_s measures
    # suspicion -> first post-failover step); failover_halted is TERMINAL —
    # the failover breaker tripped and the runtime stopped instead of
    # flapping; shard_overflow localizes mailbox/exchange overflow to one
    # shard (the "slow, not dead" warning); `overflowed` names which
    # counter grew: "mailbox" (a bounded mailbox's slots), "spill" (the
    # spill region, where the system has one), "exchange" (a pair chunk)
    def device_suspected(self, system: str, shard: int, phi: float,
                         detector: str) -> None: ...

    def device_evicted(self, system: str, shard: int, step: int) -> None: ...

    def failover_completed(self, system: str, lost_shards, survivors: int,
                           step: int, mttr_s: float) -> None: ...

    def failover_halted(self, system: str, failovers: int,
                        reason: str) -> None: ...

    def shard_overflow(self, system: str, shard: int, mailbox_overflow: int,
                       dropped: int, overflowed=()) -> None: ...

    # elastic mesh (batched/sentinel.scale_to + batched/autoscale.py):
    # device_rejoined per device added back on a grow; mesh_expanded /
    # mesh_narrowed after the bounded-pause live re-shard resumes
    # (pause_s = drain -> first dispatch on the new mesh is ready);
    # autoscale_decision records WHY the policy acted (trigger signal +
    # its observed value) with the measured pause — the operator-facing
    # audit trail of every mesh-size change
    def device_rejoined(self, system: str, shard: int, step: int) -> None: ...

    def mesh_expanded(self, system: str, from_shards: int, to_shards: int,
                      step: int, pause_s: float, trigger: str) -> None: ...

    def mesh_narrowed(self, system: str, from_shards: int, to_shards: int,
                      step: int, pause_s: float, trigger: str) -> None: ...

    def autoscale_decision(self, system: str, direction: str, signal: str,
                           value: float, from_shards: int, to_shards: int,
                           pause_ms: float) -> None: ...

    # -- generic escape hatch ------------------------------------------------
    def event(self, name: str, **fields: Any) -> None: ...

    def events(self) -> List[Dict[str, Any]]:
        return []

    def close(self) -> None: ...


class NoOpFlightRecorder(FlightRecorder):
    """Default: every hook is a pass (ActorFlightRecorder noop parity)."""

    enabled = False


def _structured(method_name):
    def hook(self, *args, **kwargs):
        self._record(method_name, args, kwargs)
    return hook


# Recorder plumbing on the SPI that is NOT a structured hook: the **fields
# escape hatch and the buffer/lifecycle accessors.
_NON_HOOKS = frozenset({"event", "events", "close"})


def spi_hook_fields() -> Dict[str, Tuple[str, ...]]:
    """hook name -> positional field names, derived from the FlightRecorder
    SPI signatures themselves. Adding a hook to the SPI (or a field to an
    existing hook) updates every structured recorder automatically — the
    hand-maintained copy of this table used to drift one hook behind."""
    fields: Dict[str, Tuple[str, ...]] = {}
    for name, fn in vars(FlightRecorder).items():
        if name.startswith("_") or name in _NON_HOOKS or not callable(fn):
            continue
        params = tuple(inspect.signature(fn).parameters)
        fields[name] = params[1:]  # drop self
    return fields


class InMemoryFlightRecorder(FlightRecorder):
    """Bounded ring of structured events; the testkit/debug recorder."""

    _FIELDS = spi_hook_fields()

    def __init__(self, capacity: int = 4096):
        self._buf: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()

    def _record(self, name: str, args, kwargs=None) -> None:
        # dual timestamps (ISSUE 12 satellite 2): wall `ts` for humans,
        # monotonic `ts_mono` so tools/trace_export.py can align FR rows
        # with tracing spans without guessing a clock offset. Rows written
        # before this change carry `ts` only and still parse everywhere.
        ev = {"event": name, "ts": time.time(), "ts_mono": time.monotonic()}
        for field, value in zip(self._FIELDS.get(name, ()), args):
            ev[field] = value
        if kwargs:
            ev.update(kwargs)
        self._append(ev)

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            self._buf.append(ev)

    def event(self, name: str, **fields: Any) -> None:
        self._append({"event": name, "ts": time.time(),
                      "ts_mono": time.monotonic(), **fields})

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._buf)

    def of_type(self, name: str) -> List[Dict[str, Any]]:
        return [e for e in self.events() if e["event"] == name]


for _m in InMemoryFlightRecorder._FIELDS:
    setattr(InMemoryFlightRecorder, _m, _structured(_m))


class JsonlFlightRecorder(InMemoryFlightRecorder):
    """Appends every event as one JSON line (the post-mortem recorder —
    a human can `jq` the flight after a crash, like opening a .jfr)."""

    def __init__(self, path: str, capacity: int = 4096):
        super().__init__(capacity)
        self._path = path
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._fh = open(path, "a", buffering=1)
        self._flock = threading.Lock()

    def _append(self, ev: Dict[str, Any]) -> None:
        super()._append(ev)
        with self._flock:
            try:
                self._fh.write(json.dumps(ev) + "\n")
            except ValueError:  # closed file mid-shutdown
                pass

    def close(self) -> None:
        with self._flock:
            try:
                self._fh.close()
            except Exception:  # noqa: BLE001
                pass


def from_config(config) -> FlightRecorder:
    """`akka.flight-recorder.implementation`: noop (default) | memory | jsonl
    (+ `akka.flight-recorder.path` for jsonl)."""
    impl = "noop"
    path = "flight.jsonl"
    capacity = 4096
    if config is not None:
        impl = config.get_string("akka.flight-recorder.implementation", "noop")
        path = config.get_string("akka.flight-recorder.path", path)
        capacity = config.get_int("akka.flight-recorder.capacity", capacity)
    if impl == "memory":
        return InMemoryFlightRecorder(capacity)
    if impl == "jsonl":
        return JsonlFlightRecorder(path, capacity)
    return NoOpFlightRecorder()


# ------------------------------------------------------------- the span log
# The process's one log of host spans: every `trace_span` and every JAX
# compile event appends a record, the oldest fall out. It outlives the
# systems that wrote to it; a reader that needs exact sums clears it first.
# Sized so that a run's set-up records are still there when it ends: a
# chip's step driver dispatches 6-14 times a second, a toy system on a CPU
# four to five thousand times (a record is about 0.4 kB).
SPAN_LOG_CAPACITY = 16384

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_DURATION_EVENTS = frozenset({TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT,
                              CACHE_RETRIEVAL_EVENT, CACHE_SAVED_EVENT})
_COUNTED_EVENTS = frozenset({CACHE_HIT_EVENT, CACHE_MISS_EVENT})

_SPANS: deque = deque(maxlen=SPAN_LOG_CAPACITY)
_SPAN_LOCK = threading.Lock()
_SPAN_IDS = itertools.count(1)  # next() is one call: no two spans share an id
_OPEN = threading.local()       # .stack: this thread's open spans, outermost first
_LISTENING = False

# one lazy import per process, not one per span (host-only users of this
# module never pay the jax import)
_PROFILER: Any = None


def _profiler():
    global _PROFILER
    if _PROFILER is None:
        import jax.profiler as _p
        _PROFILER = _p
    return _PROFILER


def _open_spans() -> list:
    try:
        return _OPEN.stack
    except AttributeError:
        _OPEN.stack = []
        return _OPEN.stack


def _log(span_id: int, parent: int, name: str, t0: int, t1: int,
         stats: Dict[str, Any]) -> None:
    rec = (span_id, parent, threading.get_ident(), name, t0, t1, stats)
    with _SPAN_LOCK:
        _SPANS.append(rec)


def _log_event(event: str, t0: int, t1: int, stats: Dict[str, Any]) -> None:
    """A JAX monitoring event as a record under this thread's open span."""
    stack = _open_spans()
    _log(next(_SPAN_IDS), stack[-1].id if stack else 0, event, t0, t1, stats)


def _snapshot(names=None) -> List[Dict[str, Any]]:
    with _SPAN_LOCK:
        records = list(_SPANS)
    return [{"id": i, "parent": p, "thread": th, "name": n, "t0_ns": t0,
             "t1_ns": t1, "stats": dict(st)}
            for i, p, th, n, t0, t1, st in records
            if names is None or n in names]


class trace_span:
    """Context manager around a host-side region. Always: one record in the
    span log at exit (`span_log()`), with the region's start and end in
    `time.monotonic_ns()`, its id, its parent's and `stats`. While a
    jax.profiler session is open: also a `TraceAnnotation` of the same name
    in that trace, alongside the XLA ops the region launches, with the
    keyword arguments as the event's stats (the step drivers pass `step0`,
    `steps`: which device executions this dispatch launched) and the id as
    `span_id`. What is known only at exit (`compiled`, or anything the
    caller adds to `.stats` inside the region) is in the log's record and
    not in the annotation.

    `compiled` / `compile_s` / `program` count the backend compilations or
    cache loads that fell inside the region on this thread, once
    `listen_for_compiles()` ran: 0 in steady state."""

    __slots__ = ("name", "stats", "id", "parent", "t0", "t1", "compiled",
                 "compile_s", "program", "_cm")

    def __init__(self, name: str, **stats):
        self.name = name
        self.stats = stats
        self.id = self.parent = self.t0 = self.t1 = 0
        self.compiled = 0
        self.compile_s = 0.0
        self.program = None
        self._cm = None

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def __enter__(self):
        stack = _open_spans()
        self.parent = stack[-1].id if stack else 0
        self.id = next(_SPAN_IDS)
        stack.append(self)
        try:
            self._cm = _profiler().TraceAnnotation(
                self.name, span_id=self.id, **self.stats)
            self._cm.__enter__()
        except Exception:  # noqa: BLE001 — tracing must never break the step
            self._cm = None
        self.t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic_ns()
        if self._cm is not None:
            try:
                self._cm.__exit__(*exc)
            except Exception:  # noqa: BLE001
                pass
        stack = _open_spans()
        if stack and stack[-1] is self:
            stack.pop()
        stats = self.stats
        if self.compiled:
            stats = dict(stats, compiled=self.compiled,
                         compile_s=self.compile_s, program=self.program)
        _log(self.id, self.parent, self.name, self.t0, self.t1, stats)
        return False


def span_log() -> List[Dict[str, Any]]:
    """A snapshot of the log, oldest first: `id`, `parent` (0 for a root),
    `thread`, `name`, `t0_ns`, `t1_ns` (`time.monotonic_ns()`), `stats`.
    Records are appended as they END, so a parent follows its children."""
    return _snapshot()


def clear_span_log() -> None:
    with _SPAN_LOCK:
        _SPANS.clear()


# ---------------------------------------------------------- the compile log
def _on_duration(event: str, secs: float, **kw) -> None:
    if event not in _DURATION_EVENTS:
        return
    now = time.monotonic_ns()
    secs = float(secs)
    stats: Dict[str, Any] = {}
    if event == COMPILE_EVENT:
        stack = _open_spans()
        if stack:  # which dispatch compiled: the log's `steps` of a new n
            stats.update(stack[-1].stats, span=stack[-1].name)
        for span in stack:
            span.compiled += 1
            span.compile_s += secs
            span.program = kw.get("fun_name")
    stats["secs"] = secs
    if "fun_name" in kw:
        stats["fun_name"] = kw["fun_name"]
    _log_event(event, now - int(secs * 1e9), now, stats)


def _on_event(event: str, **kw) -> None:
    if event in _COUNTED_EVENTS:
        now = time.monotonic_ns()
        _log_event(event, now, now, {})


def listen_for_compiles() -> None:
    """Register, once a process, listeners for JAX's monitoring events: each
    of trace, lower, backend compile (a persistent-cache load is inside that
    bracket), cache retrieval, time saved, cache hit and cache miss becomes
    a span-shaped record of the log, named for the event, and each backend
    compile counts into the spans open on its thread. The step drivers call
    this when they are built; JAX calls a listener on the compiling thread."""
    global _LISTENING
    with _SPAN_LOCK:
        if _LISTENING:
            return
        _LISTENING = True
    import jax.monitoring as monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def _traced_name(fun_name: str) -> str:
    """`jit(_run_impl)` (lower, compile) -> `_run_impl` (trace)."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


def compile_log() -> List[Dict[str, Any]]:
    """The log's compile events as one row a program compiled or loaded,
    oldest first: `program` (JAX's `fun_name`, `jit(_run_impl)`), `t_ns`
    (when the backend compile ended, `time.monotonic_ns()`), `trace_s`,
    `lower_s`, `compile_s` (the backend compile's bracket; a cache load is
    inside it), `cache` (`"hit"`, `"miss"`: the entry was written, or None:
    no persistent cache, or an entry under its thresholds), `retrieval_s`
    and `saved_s` of a hit, and `span`, the name of the span it fell in,
    with that span's stats: a `run(n)` with an `n` not seen before is a row
    with `span` `akka.device.run[n]` and `steps` n. Inner functions traced
    on the way (`jnp.where` inside the step) have trace events and no row:
    their seconds are inside the outer program's."""
    rows: List[Dict[str, Any]] = []
    pending: Dict[int, Dict[str, Any]] = {}
    for rec in _snapshot(_DURATION_EVENTS | _COUNTED_EVENTS):
        event, stats = rec["name"], rec["stats"]
        p = pending.setdefault(rec["thread"], {"trace": {}, "lower": {}})
        if event == TRACE_EVENT:
            p["trace"][stats.get("fun_name")] = stats["secs"]
        elif event == LOWER_EVENT:
            p["lower"][stats.get("fun_name")] = stats["secs"]
        elif event == CACHE_HIT_EVENT:
            p["cache"] = "hit"
        elif event == CACHE_MISS_EVENT:
            p["cache"] = "miss"
        elif event == CACHE_RETRIEVAL_EVENT:
            p["retrieval_s"] = stats["secs"]
        elif event == CACHE_SAVED_EVENT:
            p["saved_s"] = stats["secs"]
        else:  # COMPILE_EVENT closes the row
            program = stats.pop("fun_name", None)
            row = {"program": program, "t_ns": rec["t1_ns"],
                   "trace_s": p["trace"].pop(_traced_name(program or ""), 0.0),
                   "lower_s": p["lower"].pop(program, 0.0),
                   "compile_s": stats.pop("secs"),
                   "cache": p.pop("cache", None),
                   "retrieval_s": p.pop("retrieval_s", 0.0),
                   "saved_s": p.pop("saved_s", 0.0),
                   "span": stats.pop("span", None)}
            row.update(stats)  # the enclosing span's: step0, steps, ...
            rows.append(row)
    return rows


# ------------------------------------------- the log on the profiler's clock
def trace_span_starts(path: str) -> Dict[int, int]:
    """`span_id` -> start (ns, the trace's clock) of every host event of a
    profile (`.xplane.pb`) that carries the stat: the `trace_span`s that
    ran while the session was open."""
    data = _profiler().ProfileData.from_file(path)
    starts: Dict[int, int] = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                for key, value in ev.stats:
                    if key == "span_id":
                        starts[int(value)] = int(ev.start_ns)
    return starts


def trace_clock_offset_ns(starts: Dict[int, int]) -> Optional[int]:
    """What to add to a `time.monotonic_ns()` reading to land on the clock
    of the profile `starts` was read from (`trace_span_starts`): the median,
    over the spans that are in both the log and the trace, of trace start
    less logged start. None where no span is in both."""
    diffs = sorted(starts[r["id"]] - r["t0_ns"] for r in span_log()
                   if r["id"] in starts)
    if not diffs:
        return None
    return diffs[len(diffs) // 2]


def to_trace_ns(t_monotonic_s: float, offset_ns: int) -> int:
    """A `time.monotonic()` instant (seconds) on the trace's clock (ns)."""
    return int(round(t_monotonic_s * 1e9)) + offset_ns
