"""BatchedRuntimeHandle: the host-ActorRef ↔ device-row bridge.

This is the mechanism behind the `tpu-batched` dispatcher type (VERDICT r1
item 2): Props carrying a device behavior spawn rows in the dispatcher-owned
BatchedSystem behind ordinary ActorRefs, `ref.tell` routes through the native
stager into the device inbox, and `ask` completes via promise rows read back
after a step — the reference call stack being replaced is
ActorRef.! → Dispatcher.dispatch → Mailbox.run → receive
(dispatch/Dispatchers.scala:121-259 is the extension seam; SURVEY.md §3.2 the
hot path).

Pieces:
- MessageCodec: host message object ↔ (mtype, payload row). The default
  codec passes through (mtype, payload) tuples and bare numbers/arrays.
- BatchedRuntimeHandle: lazy-built BatchedSystem + row allocation + promise
  rows for ask + an auto-pump thread that steps the device while host work
  is pending (the registerForExecution analogue: work present → schedule).
- DeviceActorRef: a watchable ActorRef bound to one row (FunctionRef-style
  watcher bookkeeping — late tells after stop go to dead letters).
- DeviceBlockRef: one ref addressing a spawned block (bulk tells broadcast;
  `block[i]` derives the per-row ref) — the 1M-actor case never allocates a
  million Python objects unless asked to.

Ask/reply convention: the encoded payload's LAST column carries the reply-to
row id as a value cast; replying behaviors emit to
`payload[-1].astype(int32)`. Promise rows run a reduce-kind behavior that
latches the first reply (pattern/AskSupport.scala:476 parity). The value
cast is exact only while every row id fits the payload dtype's integer
range (2^24 for float32, 2^11 for float16, 2^8 for bfloat16) — the handle
VALIDATES this at construction and refuses capacities whose reply ids
would silently round (PromiseActorRef identity is never lossy,
AskSupport.scala:476)."""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..actor.messages import DeadLetter, Terminated
from ..dispatch import sysmsg
from ..actor.ref import ActorRef, InternalActorRef
from ..pattern.backoff import backoff_delay
from ..pattern.circuit_breaker import (CircuitBreaker,
                                       CircuitBreakerOpenException)
from .behavior import BatchedBehavior, Emit, behavior as behavior_deco
from .core import BatchedSystem
from .metrics_slab import ASK_ARM_COL
from .supervision import ATT_FAILED_BIT, ATT_FLAGS, ATT_LATCH_BIT

I32 = jnp.int32
F32 = jnp.float32


class AskPoolExhausted(RuntimeError):
    """Every promise row is claimed by an in-flight (or quarantined) ask:
    the ask fails FAST and TYPED instead of queueing or burning its
    timeout. Admission layers (akka_tpu/gateway/admission.py) catch this
    to shed load — it is the backpressure signal for the ask pool, the
    way mailbox_overflow is for tells. Sized by the tpu-batched
    dispatcher's `promise-rows` config key."""


class RecoveredAskLost(Exception):
    """Failed into ask futures that were outstanding when the runtime was
    restored from a checkpoint: promise-row latch state is overwritten by
    the snapshot, so the reply can never arrive — the waiter is failed
    fast and distinguishably instead of hanging until its timeout (the
    recovery analogue of AskSupport failing asks to terminated refs)."""


# --------------------------------------------------------------------- codec
class MessageCodec:
    """Host message object ↔ fixed-schema device row."""

    def encode(self, message: Any, reply_to: int = -1) -> Tuple[int, np.ndarray]:
        raise NotImplementedError

    def decode(self, payload: np.ndarray) -> Any:
        raise NotImplementedError


class DefaultCodec(MessageCodec):
    """(mtype, payload) tuples pass through; bare scalars/arrays get type 0.
    reply_to (when >= 0) is written into the last payload column."""

    def __init__(self, payload_width: int, dtype=np.float32):
        self.payload_width = payload_width
        self.dtype = np.dtype(dtype)

    def encode(self, message: Any, reply_to: int = -1) -> Tuple[int, np.ndarray]:
        if isinstance(message, tuple) and len(message) == 2 and \
                isinstance(message[0], (int, np.integer)):
            mtype, body = message
        else:
            mtype, body = 0, message
        row = np.zeros(self.payload_width, self.dtype)
        arr = np.atleast_1d(np.asarray(body, self.dtype)).reshape(-1)
        row[: arr.shape[0]] = arr[: self.payload_width]
        if reply_to >= 0:
            row[-1] = reply_to
        return int(mtype), row

    def decode(self, payload: np.ndarray) -> Any:
        return payload


def reply_dst(payload) -> Any:
    """Helper for behaviors: the reply-to row id encoded in the payload's
    last column (ask convention)."""
    return payload[-1].astype(jnp.int32)


# The reduce-delivery mode of every runtime that carries promise rows (the
# bridge here, DeviceShardRegion, a MeshSentinel with promise_rows > 0). The
# ask protocol reads the reply-to row id back through `inbox.sum`, so a
# segment's sum must be exact ALONE. The merge/sort kernels take a segment's
# sum as a difference of one running prefix over ALL of a step's messages,
# exact only while that prefix stays inside the payload dtype's integer
# range (2^24 for f32): at 2^20 rows, 17 asks in one step already misroute
# replies. Scatter-add accumulates each segment alone. These layers pass
# the mode themselves and expose no `delivery` option, so no caller can ask
# them for merge or sort; on the CPU "auto" is scatter anyway. The repair
# that frees the mode is ROADMAP A3: carry the reply id outside the sum.
ASK_DELIVERY = "scatter"


def max_exact_row_id(dtype) -> int:
    """Largest row id a value-cast into `dtype` roundtrips exactly.

    Integers: the dtype's max. Floats: every integer up to
    2^(mantissa_bits + 1) is exactly representable (float32 -> 2^24,
    float16 -> 2^11, bfloat16 -> 2^8)."""
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.integer):
        return int(jnp.iinfo(dt).max)
    return 1 << (jnp.finfo(dt).nmant + 1)


def read_promise_block(state, base: int, n: int, replied_col: str,
                       reply_col: Optional[str] = None):
    """One static-slice host fetch of a promise block's latch (and,
    optionally, reply) columns: constant shape -> one XLA program ever —
    a per-waiter-count gather would recompile for every distinct shape.
    Shared by the bridge's `_resolve_waiters` drain, the region's batched
    ask engine
    (sharding/ask_batch.py) and its retired-slot reclaim. Returns
    `(replied, replies)` numpy arrays (`replies` is None when `reply_col`
    is not requested); the device_get blocks until every enqueued step
    has produced the newest state handle."""
    replied = np.asarray(jax.device_get(state[replied_col][base:base + n]))
    if reply_col is None:
        return replied, None
    replies = np.asarray(jax.device_get(state[reply_col][base:base + n]))
    return replied, replies


def _slice_init(value, idx_or_mask, n_rows: int):
    """Select the per-row slice of an init value: arrays whose leading dim
    matches the spawn's row count are per-row (spawn_block broadcast
    semantics); anything else is a scalar/broadcast value."""
    v = np.asarray(value)
    if v.ndim >= 1 and v.shape[0] == n_rows:
        return v[idx_or_mask]
    return value


# ----------------------------------------------------------------- the handle
class _SpawnRecord:
    __slots__ = ("behavior", "n", "init_state", "rows")

    def __init__(self, behavior, n, init_state, rows):
        self.behavior = behavior
        self.n = n
        self.init_state = init_state
        self.rows = rows


class BatchedRuntimeHandle:
    """Owns the device runtime for one tpu-batched dispatcher.

    The runtime is built lazily at the first step so behaviors registered by
    any spawn order compile into one lax.switch; spawning a NEW behavior
    type after the build triggers a rebuild that preserves all state, rows
    and in-flight inbox contents (behavior ids are append-only, so existing
    behavior_id columns stay valid).
    """

    PROMISE_REPLY = "__promise_reply"
    PROMISE_REPLIED = "__promise_replied"

    def __init__(self, capacity: int = 1 << 20, payload_width: int = 8,
                 out_degree: int = 1, host_inbox: int = 4096,
                 mailbox_slots: int = 0, promise_rows: int = 256,
                 auto_step_interval: float = 0.001,
                 payload_dtype=jnp.float32, event_stream=None,
                 flight_recorder=None, failure_policy: str = "restart",
                 pipeline_depth: int = 2,
                 delivery_backend: Optional[str] = None,
                 checkpoint_interval_steps: int = 0,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_keep: int = 3,
                 wal_fsync_every_n: int = 1,
                 sentinel_threshold: float = 8.0,
                 sentinel_heartbeat_interval: float = 0.1,
                 sentinel_acceptable_pause: float = 3.0,
                 sentinel_max_failovers: int = 3,
                 sentinel_depth_recovery_rounds: int = 64,
                 metrics_enabled: bool = False,
                 metrics_registry=None):
        self.capacity = capacity
        self.payload_width = payload_width
        self.out_degree = out_degree
        self.host_inbox = host_inbox
        self.mailbox_slots = mailbox_slots
        self.promise_rows_n = promise_rows
        self.auto_step_interval = auto_step_interval
        self.payload_dtype = payload_dtype
        # depth-k dispatch pipeline: the pump and step(n) keep up to this
        # many fused flush+step programs in flight before blocking on the
        # oldest one's attention word (1 = the old synchronous behavior)
        self.pipeline_depth = max(1, int(pipeline_depth))
        # ops/segment.py kernel seam, forwarded to the BatchedSystem
        self.delivery_backend = delivery_backend
        # ask reply routing rides a VALUE CAST of the reply row id into the
        # payload dtype's last column (VERDICT r3 #6): refuse, at build
        # time, any capacity whose ids would round — a bf16 payload system
        # with 1M rows would otherwise corrupt reply routing silently
        limit = max_exact_row_id(payload_dtype)
        if capacity - 1 > limit:
            raise ValueError(
                f"capacity {capacity} exceeds the exactly-representable "
                f"row-id range of payload_dtype "
                f"{jnp.dtype(payload_dtype).name} (max id {limit}): ask "
                f"reply ids are value-cast into the last payload column "
                f"and would silently round — use float32/int32 payloads "
                f"or capacity <= {limit + 1}")
        self.event_stream = event_stream
        self.flight_recorder = flight_recorder
        if failure_policy not in ("restart", "stop", "suspend"):
            raise ValueError(f"unknown failure_policy {failure_policy!r}")
        self.failure_policy = failure_policy
        self._reported_failed: set = set()  # rows already published
        # (rows, init_state) per spawn: a restart must re-apply the
        # spawn-time init (Props re-instantiation parity), not reset to
        # zeros. Rows are stored explicitly — free-list reuse makes spawn
        # results non-contiguous.
        self._spawn_inits: List[Tuple[np.ndarray, Dict[str, Any]]] = []
        self.default_codec = DefaultCodec(payload_width,
                                          np.dtype(jnp.dtype(payload_dtype)))

        self._behaviors: List[BatchedBehavior] = []
        self._spawns: List[_SpawnRecord] = []
        self._next_row = 0
        self._runtime: Optional[BatchedSystem] = None
        self._lock = threading.RLock()

        # detection-only shard sentinel (batched/sentinel.py): every drain
        # feeds the [ATT_WORDS] word's progress lane to a phi-accrual
        # detector, so a hung or preempted device surfaces as a
        # device_suspected flight-recorder event instead of silent pump
        # starvation. A single-device handle has nowhere to fail over TO —
        # eviction/rebuild lives in MeshSentinel; max_failovers is carried
        # in stats for operator parity with the sharded runtime.
        from .sentinel import ShardProgressMonitor
        self.sentinel_max_failovers = int(sentinel_max_failovers)
        # parity carry like max_failovers: the depth degrade-ladder only
        # runs in MeshSentinel, but the knob rides the same config path
        self.sentinel_depth_recovery_rounds = int(sentinel_depth_recovery_rounds)
        self._sentinel = ShardProgressMonitor(
            threshold=sentinel_threshold,
            heartbeat_interval=sentinel_heartbeat_interval,
            acceptable_pause=sentinel_acceptable_pause)
        self._sentinel_reported: set = set()

        # ask machinery
        self._promise_base: Optional[int] = None
        self._promise_free: List[int] = []
        self._waiters: Dict[int, Future] = {}       # promise row -> future
        self._waiter_deadlines: Dict[int, float] = {}
        # timed-out asks whose reply may still be in flight on device: the
        # slot is quarantined (NOT freed) until the late reply latches or a
        # hard deadline passes — freeing immediately could hand the slot to
        # a new ask that then completes with the previous question's answer
        self._promise_zombies: Dict[int, float] = {}
        self._stat_ask_exhausted = 0  # typed fast-fails (AskPoolExhausted)

        # pump
        self._pump_thread: Optional[threading.Thread] = None
        self._pump_wake = threading.Event()
        self._shutdown = False
        self._pending_tells = 0  # python-staging path hint
        # serializes device steps: the auto-pump and explicit step() must
        # never run the jitted step concurrently (donated buffers)
        self._step_lock = threading.Lock()

        # pipeline telemetry (plain ints mutated under the GIL; consumed
        # by pipeline_stats() and the device_pipeline flight-recorder
        # event). wide_resolves counts drains that paid the wide promise
        # readback; host_checks the drains that got away with host-only
        # deadline bookkeeping — the ratio is the attention word's win.
        self._stat_steps = 0
        self._stat_drains = 0
        self._stat_wide_resolves = 0
        self._stat_host_checks = 0
        self._stat_reported = np.zeros((4,), np.int64)  # FR delta snapshot

        # auto-checkpoint cadence (ISSUE 4 tentpole #4): every
        # checkpoint_interval_steps dispatched steps the pump takes a
        # barrier snapshot into checkpoint_dir, keeping checkpoint_keep of
        # them. checkpoint_dir alone (interval 0) still arms the
        # write-ahead tell journal for manual checkpoint()/restore().
        # Snapshot-IO failures DEGRADE (circuit breaker + exponential
        # backoff + flight-recorder warning) — the step loop never stalls
        # on a sick filesystem.
        self.checkpoint_interval_steps = max(0, int(checkpoint_interval_steps))
        self.checkpoint_dir = checkpoint_dir or None
        self.checkpoint_keep = max(1, int(checkpoint_keep))
        self.wal_fsync_every_n = max(1, int(wal_fsync_every_n))
        self._journal = None  # persistence.tell_journal.TellJournal
        self._ckpt_last_step = 0
        self._ckpt_failures = 0        # consecutive failures (backoff rank)
        self._ckpt_retry_at = 0.0      # monotonic gate after a failure
        # scheduler=None: only the sync path is used, which never schedules
        self._ckpt_breaker = CircuitBreaker(
            None, max_failures=3, call_timeout=60.0, reset_timeout=5.0,
            exponential_backoff_factor=2.0, max_reset_timeout=300.0)
        self._ckpt_stats = {"checkpoints": 0, "failures": 0,
                            "last_step": 0, "last_duration_s": 0.0,
                            "last_size_bytes": 0, "last_path": None}

        # unified telemetry plane (event/metrics.py + batched/metrics_slab):
        # metrics_enabled compiles the device slab into the step; the
        # registry absorbs the *_stats() dicts as collectors and ingests
        # the slab at the pump's busy->idle edge and the checkpoint
        # barrier. A caller-supplied registry is shared (the dispatcher
        # owns its sinks); otherwise the handle owns one and closes it.
        self.metrics_enabled = bool(metrics_enabled)
        self._owns_registry = metrics_registry is None and self.metrics_enabled
        if metrics_registry is None and self.metrics_enabled:
            from ..event.metrics import MetricsRegistry
            metrics_registry = MetricsRegistry()
        self.metrics_registry = metrics_registry
        if self.metrics_registry is not None:
            reg = self.metrics_registry
            reg.register_collector("pipeline", self.pipeline_stats)
            reg.register_collector("device_host", self.host_stats)
            reg.register_collector("checkpoint", self.checkpoint_stats)
            reg.register_collector("sentinel", self._sentinel_metrics)
            reg.register_collector("ask_pool", self.ask_pool_stats)

    # -------------------------------------------------------------- behaviors
    def _behavior_index(self, b: BatchedBehavior) -> int:
        with self._lock:  # registration races spawn()/runtime() callers
            for i, x in enumerate(self._behaviors):
                if x is b:
                    return i
            self._behaviors.append(b)
            if self._runtime is not None:
                self._rebuild()
            return len(self._behaviors) - 1

    def _promise_behavior(self) -> BatchedBehavior:
        p_w = self.payload_width
        reply_col, replied_col = self.PROMISE_REPLY, self.PROMISE_REPLIED

        @behavior_deco("__promise",
                       {reply_col: ((p_w,), self.payload_dtype),
                        replied_col: ((), jnp.bool_)})
        def promise(state, inbox, ctx):
            got = inbox.count > 0
            # latch the FIRST reply (AskSupport: first answer wins)
            take = got & ~state[replied_col]
            return ({reply_col: jnp.where(take, inbox.sum, state[reply_col]),
                     replied_col: state[replied_col] | got},
                    Emit.none(self.out_degree, p_w))

        return promise

    # ------------------------------------------------------------------ spawn
    def spawn(self, b: BatchedBehavior, n: int = 1,
              init_state: Optional[Dict[str, Any]] = None) -> np.ndarray:
        """Allocate n rows of behavior b. Returns global row ids."""
        with self._lock:
            self._behavior_index(b)
            if self._runtime is not None:
                with self._step_lock:  # slab writes must not race a step
                    rows = self._runtime.spawn_block(
                        self._behaviors.index(b), n, init_state)
                if init_state:
                    self._spawn_inits.append(
                        (np.asarray(rows, np.int32), dict(init_state)))
                return rows
            # pre-build: the top promise_rows_n rows are reserved for ask()
            if self._next_row + n > self.capacity - self.promise_rows_n:
                raise RuntimeError("device actor capacity exhausted")
            rows = np.arange(self._next_row, self._next_row + n,
                             dtype=np.int32)
            self._next_row += n
            self._spawns.append(_SpawnRecord(b, n, init_state, rows))
            if init_state:
                self._spawn_inits.append((rows.copy(), dict(init_state)))
            return rows

    def stop_rows(self, rows) -> None:
        self._ensure_runtime()
        arr = np.atleast_1d(np.asarray(rows, np.int32))
        with self._step_lock:
            # re-resolve under the lock: a concurrent _rebuild (which holds
            # this lock) may have swapped the runtime since the build check
            self._runtime.stop_block(arr)
        with self._lock:
            # prune init records UNDER THE SAME LOCK spawn() appends with —
            # a recycled row's NEW occupant must never inherit the old
            # spawn's init values on restart
            pruned = []
            for rec_rows, init in self._spawn_inits:
                mask = ~np.isin(rec_rows, arr)
                if mask.all():
                    pruned.append((rec_rows, init))
                elif mask.any():
                    # per-row array inits stay aligned with their rows
                    pruned.append((rec_rows[mask],
                                   {c: _slice_init(v, mask, rec_rows.size)
                                    for c, v in init.items()}))
            self._spawn_inits = pruned

    def generation_of(self, rows) -> np.ndarray:
        """Incarnation generations for rows (pre-build rows are gen 0 —
        nothing can have stopped yet). Does NOT force the runtime build."""
        arr = np.atleast_1d(np.asarray(rows, np.int64))
        with self._lock:
            if self._runtime is None:
                return np.zeros(arr.shape, np.int64)
            return self._runtime.generation_of(arr)

    def read_state(self, col: str, rows=None) -> np.ndarray:
        """Read state columns without racing an in-flight step's buffer
        donation. Fetches the full column and indexes host-side: dynamic
        device gathers recompile per index-shape; this is a
        debug/observation path, not the hot loop."""
        self._ensure_runtime()
        import jax as _jax
        with self._step_lock:
            full = np.asarray(_jax.device_get(self._runtime.state[col]))
        if rows is None:
            return full
        return full[np.asarray(rows)]

    # ---------------------------------------------------------------- runtime
    def _ensure_runtime(self) -> BatchedSystem:
        with self._lock:
            if self._runtime is None:
                self._build()
            return self._runtime

    @property
    def runtime(self) -> BatchedSystem:
        return self._ensure_runtime()

    def _build(self) -> None:
        behaviors = list(self._behaviors) + [self._promise_behavior()]
        rt = BatchedSystem(
            capacity=self.capacity, behaviors=behaviors,
            payload_width=self.payload_width, out_degree=self.out_degree,
            host_inbox=self.host_inbox, payload_dtype=self.payload_dtype,
            mailbox_slots=self.mailbox_slots,
            delivery=ASK_DELIVERY,
            delivery_backend=self.delivery_backend,
            # the promise-latch column feeds ATT_LATCH_BIT of the
            # attention word: the pump only pays the wide promise-block
            # readback when some row actually latched a reply
            attention_latch_col=self.PROMISE_REPLIED,
            metrics_enabled=self.metrics_enabled)
        if self.event_stream is not None:
            rt.on_dropped = self._publish_dropped
            rt.on_dead_letter = self._publish_dead_letters
        rt.flight_recorder = self.flight_recorder
        for rec in self._spawns:
            got = rt.spawn_block(behaviors.index(rec.behavior), rec.n,
                                 rec.init_state)
            assert got[0] == rec.rows[0], "spawn replay out of order"
        # promise rows live right after the replayed spawns (their slice of
        # capacity was reserved by spawn()'s pre-build check, so this cannot
        # fail after the records were consumed)
        self._promise_base = int(rt.spawn_block(
            len(behaviors) - 1, self.promise_rows_n)[0])
        self._promise_free = list(range(self.promise_rows_n))
        self._spawns.clear()  # only after full success — a retry replays
        rt.warmup()  # compile now; asks must not spend their timeout in XLA
        if self.checkpoint_dir is not None and self._journal is None:
            # WAL armed with the runtime: staged batches journal before
            # enqueue from the first tell on. An unwritable dir degrades
            # (no journal, warn) — durability is best-effort, liveness not
            try:
                from ..persistence.tell_journal import TellJournal
                self._journal = TellJournal(
                    os.path.join(self.checkpoint_dir, "tells.wal"),
                    flight_recorder=self.flight_recorder,
                    fsync_every_n=self.wal_fsync_every_n)
            except OSError as e:
                fr = self.flight_recorder
                if fr is not None and fr.enabled:
                    fr.checkpoint_failed("batched",
                                         f"journal open: {e!r}"[:200], 0)
        rt.tell_journal = self._journal
        self._runtime = rt

    def _rebuild(self) -> None:
        """A new behavior type arrived after the build: re-trace with the
        extended (append-only) behavior list, carrying over all slabs.
        Holds the step lock for the whole copy+swap — the old slabs are
        donated to any in-flight step and must not be read mid-flight."""
        with self._step_lock:
            self._rebuild_locked()

    def _rebuild_locked(self) -> None:
        old = self._runtime
        behaviors = list(self._behaviors) + [self._promise_behavior()]
        rt = BatchedSystem(
            capacity=self.capacity, behaviors=behaviors,
            payload_width=self.payload_width, out_degree=self.out_degree,
            host_inbox=self.host_inbox, payload_dtype=self.payload_dtype,
            mailbox_slots=self.mailbox_slots,
            delivery=ASK_DELIVERY,
            delivery_backend=self.delivery_backend,
            attention_latch_col=self.PROMISE_REPLIED,
            metrics_enabled=self.metrics_enabled)
        if self.event_stream is not None:
            rt.on_dropped = self._publish_dropped
        rt.flight_recorder = self.flight_recorder
        for col, arr in old.state.items():
            if col in rt.state:
                rt.state[col] = arr
        # the promise behavior moved to the new tail index: remap ids
        old_promise_idx = len(old.behaviors) - 1
        new_promise_idx = len(behaviors) - 1
        bid = old.behavior_id
        rt.behavior_id = jnp.where(bid == old_promise_idx, new_promise_idx, bid)
        rt.alive = old.alive
        rt.inbox_dst = old.inbox_dst
        rt.inbox_type = old.inbox_type
        rt.inbox_payload = old.inbox_payload
        rt.inbox_valid = old.inbox_valid
        rt.step_count = old.step_count
        rt.mail_dropped = old.mail_dropped
        rt.spill_stats = old.spill_stats
        # cumulative telemetry survives the swap: supervision counters (and
        # the flight-recorder's delta snapshot, so the next report doesn't
        # re-emit history) plus the newest attention word — pipelined
        # callers holding OLD attention handles stay valid regardless
        # (non-donated outputs are never invalidated by the swap)
        rt.sup_counts = old.sup_counts
        rt._sup_reported = old._sup_reported
        rt.attention = old.attention
        # the metric slab and sojourn stamps survive too — cumulative
        # telemetry, exactly like sup_counts; the drain epoch bookmark
        # rides so the swap doesn't force a spurious re-ingest
        rt.metrics = old.metrics
        rt.metrics_epoch = old.metrics_epoch
        rt.inbox_enq = old.inbox_enq
        rt._metrics_seen_epoch = old._metrics_seen_epoch
        rt._next_row = old._next_row
        rt._free_rows = list(old._free_rows)
        # tells staged since the last step must survive the swap (the
        # docstring promises in-flight contents are preserved), and a tell
        # racing this rebuild through a stale runtime reference must not
        # vanish: the staging buffers are SHARED by reference — old and new
        # runtime point at the same stager / staging list / lock, so late
        # producers land in the buffers the next flush drains
        rt._stager = old._stager
        rt._host_staged = old._host_staged
        rt._lock = old._lock
        rt._dropped_host = old._dropped_host
        # incarnation identity survives the swap (same rows, same history)
        rt._generation = old._generation
        rt.dead_lettered = old.dead_lettered
        rt.on_dead_letter = old.on_dead_letter
        # recovery bookkeeping survives too: the dispatched-step counter
        # keeps journal records monotonic, and the WAL rides the new
        # runtime so tells keep journaling across the swap
        rt._host_step = old._host_step
        rt.tell_journal = old.tell_journal
        rt.warmup()
        self._runtime = rt

    def _publish_dropped(self, n: int) -> None:
        es = self.event_stream
        if es is not None:
            es.publish(DroppedDeviceMessages(n))

    def _publish_dead_letters(self, n: int) -> None:
        es = self.event_stream
        if es is not None:
            es.publish(DeviceDeadLetters(n))

    # ------------------------------------------------------------------- tell
    def tell(self, row: int, message: Any,
             codec: Optional[MessageCodec] = None, expect_gen=None) -> None:
        mtype, payload = (codec or self.default_codec).encode(message)
        self._ensure_runtime()
        self._stage_tell(row, payload, mtype, expect_gen)
        self._wake_pump()

    def tell_rows(self, rows: np.ndarray, message: Any,
                  codec: Optional[MessageCodec] = None, expect_gen=None) -> None:
        mtype, payload = (codec or self.default_codec).encode(message)
        self._ensure_runtime()
        self._stage_tell(rows, payload, mtype, expect_gen)
        self._wake_pump()

    def _stage_tell(self, dst, payload, mtype, expect_gen) -> None:
        """Stage + count atomically under the step lock: an enqueue zeroes
        `_pending_tells` for exactly the tells ITS flush drains — staging
        outside the lock could land a tell after the drain while its
        increment raced before the zero, stranding a staged-but-uncounted
        row with no pump hint until unrelated traffic arrives. The lock is
        held only for the memcpy-sized stage (never nested inside
        `_lock`); `_has_pending` additionally checks the staging buffers
        themselves, so the counter is a wake hint, not ground truth."""
        with self._step_lock:
            rt = self._runtime  # re-resolve: rebuild swaps under this lock
            rt.tell(dst, payload, mtype, expect_gen=expect_gen)
            self._pending_tells += 1

    # -------------------------------------------------------------------- ask
    def ask(self, row: int, message: Any, timeout: float = 5.0,
            codec: Optional[MessageCodec] = None, expect_gen=None) -> Future:
        rt0 = self._ensure_runtime()
        fut: Future = Future()
        if expect_gen is not None and \
                int(rt0.generation_of(row)[0]) != int(expect_gen):
            # stale incarnation: fail fast instead of burning the timeout
            # (AskSupport: ask to a terminated ref fails the future)
            rt0.tell(row, np.zeros(self.payload_width, np.float32),
                     expect_gen=expect_gen)  # count + publish the dead letter
            fut.set_exception(RuntimeError(
                f"ask to dead incarnation of device row {row} "
                f"(expected gen {expect_gen})"))
            return fut
        with self._lock:
            if not self._promise_free:
                self._stat_ask_exhausted += 1
                fut.set_exception(AskPoolExhausted(
                    f"promise rows exhausted ({self.promise_rows_n} in "
                    f"flight; raise the dispatcher's promise-rows key)"))
                return fut
            slot = self._promise_free.pop()
        prow = self._promise_base + slot
        c = codec or self.default_codec
        # reset the latch before reuse — under the step lock: the state
        # arrays are donated to any in-flight step and must not be touched
        # mid-flight (and the runtime is re-resolved under the lock so a
        # concurrent rebuild can't hand us dropped slabs)
        with self._step_lock:
            rt = self._runtime
            rt.state[self.PROMISE_REPLIED] = \
                rt.state[self.PROMISE_REPLIED].at[prow].set(False)
            if self.metrics_enabled:
                # arm the ask-latency clock: the slab histograms
                # (latch-flip step - this stamp) when the reply lands
                # (metrics_slab HIST_ASK)
                rt.state[ASK_ARM_COL] = \
                    rt.state[ASK_ARM_COL].at[prow].set(rt._host_step)
        mtype, payload = c.encode(message, reply_to=prow)
        with self._lock:
            self._waiters[prow] = (fut, c)
            # deadline None = clock starts at the first completed step, so
            # jit compile time never eats the ask budget — the timeout
            # measures device steps, not XLA compiles
            self._waiter_deadlines[prow] = (None, timeout)
        # expect_gen rides to the STAGE-TIME check too: the entry check
        # above fails fast, this closes the remaining TOCTOU window
        # against a concurrent stop+respawn of the row
        rt.tell(row, payload, mtype, expect_gen=expect_gen)
        self._wake_pump()
        return fut

    def ask_sync(self, row: int, message: Any, timeout: float = 5.0,
                 codec: Optional[MessageCodec] = None) -> Any:
        return self.ask(row, message, timeout, codec).result(timeout + 1.0)

    def _resolve_waiters(self) -> None:
        with self._lock:
            waiting = list(self._waiters.items())
            have_zombies = bool(self._promise_zombies)
        if not waiting and not have_zombies:
            return
        base, np_ = self._promise_base, self.promise_rows_n
        with self._step_lock:  # state reads must not race donation
            rt = self._runtime  # re-resolve: rebuild swaps under lock
            replied_blk, replies_blk = read_promise_block(
                rt.state, base, np_, self.PROMISE_REPLIED,
                self.PROMISE_REPLY)
        replied = [replied_blk[r - base] for r, _ in waiting]
        replies = [replies_blk[r - base] for r, _ in waiting]
        now = time.monotonic()
        clear_slots: List[int] = []
        for (prow, (fut, c)), done, reply in zip(waiting, replied, replies):
            if not done:
                deadline, timeout = self._waiter_deadlines.get(
                    prow, (now, 0.0))
                if deadline is None:
                    # first post-step visit: start the timeout clock now
                    with self._lock:
                        if prow in self._waiter_deadlines:
                            self._waiter_deadlines[prow] = (now + timeout,
                                                            timeout)
                    continue
                if now <= deadline:
                    continue
            # atomic claim: only the thread that actually pops the waiter
            # completes the future and releases the slot (the pump and an
            # explicit step() caller may resolve concurrently)
            with self._lock:
                if self._waiters.pop(prow, None) is None:
                    continue  # another resolver claimed it
                _, timeout = self._waiter_deadlines.pop(prow, (0.0, 0.0))
                if done:
                    self._promise_free.append(prow - self._promise_base)
                    clear_slots.append(prow - self._promise_base)
                else:
                    # timed out with the reply possibly still in flight:
                    # quarantine the slot until the late reply latches (or
                    # a hard deadline passes) so the next ask can't receive
                    # this question's answer
                    self._promise_zombies[prow] = now + max(5.0 * timeout,
                                                            30.0)
            if done:
                if not fut.done():
                    fut.set_result(c.decode(reply))
            elif not fut.done():
                from ..pattern.ask import AskTimeoutException
                fut.set_exception(AskTimeoutException(
                    f"device ask timed out after [{timeout}s]"))
        # reap quarantined slots: a latched late reply (or the hard
        # deadline) makes the slot safe to reuse — ask() re-arms the latch
        with self._lock:
            for prow, kill_at in list(self._promise_zombies.items()):
                if replied_blk[prow - base] or now > kill_at:
                    del self._promise_zombies[prow]
                    self._promise_free.append(prow - base)
                    if replied_blk[prow - base]:
                        clear_slots.append(prow - base)
        # lower the consumed latches so ATT_LATCH_BIT drops once every
        # resolved reply is read — without this the promise behavior's
        # sticky `replied` flag would keep the bit raised forever and every
        # later drain would pay the wide readback above for nothing
        if clear_slots:
            self._clear_latches(clear_slots)

    def _clear_latches(self, slots: List[int]) -> None:
        """Lower PROMISE_REPLIED for freed slots. One static-shape masked
        update over the whole promise block (a per-slot-count scatter
        would recompile per shape); under the step lock it consumes the
        NEWEST state handle, so it orders after every enqueued step.
        Slots still owned by a live ask are deliberately untouched: only
        slots just returned to the free list (or reaped zombies whose late
        reply was observed) are cleared, so a latch racing in from a
        concurrent ask can never be lost."""
        mask = np.zeros((self.promise_rows_n,), np.bool_)
        mask[np.asarray(slots, np.int64)] = True
        base, np_ = self._promise_base, self.promise_rows_n
        m = jnp.asarray(mask)
        with self._step_lock:
            rt = self._runtime  # re-resolve: rebuild swaps under lock
            col = rt.state[self.PROMISE_REPLIED]
            blk = jnp.where(m, False, jax.lax.dynamic_slice(col, (base,),
                                                            (np_,)))
            rt.state[self.PROMISE_REPLIED] = \
                jax.lax.dynamic_update_slice(col, blk, (base,))

    # ------------------------------------------------------------------- pump
    def _wake_pump(self) -> None:
        if self._pump_thread is None:
            with self._lock:
                if self._pump_thread is None and not self._shutdown:
                    t = threading.Thread(target=self._pump_loop,
                                         name="akka-tpu-device-pump",
                                         daemon=True)
                    self._pump_thread = t
                    t.start()
        self._pump_wake.set()

    def _has_pending(self) -> bool:
        return bool(self._waiters) or self._fresh_tells()

    def _fresh_tells(self) -> bool:
        """Staged-but-unflushed tells, read from the staging buffers
        themselves (native stager length, Python staging list) plus the
        `_pending_tells` wake hint — the buffers are authoritative, so a
        hint lost to a race can never strand staged mail."""
        rt = self._runtime
        if rt is None:
            return False
        if rt._stager is not None and len(rt._stager) > 0:
            return True
        if self._pending_tells > 0:
            return True
        return bool(rt._host_staged)

    def _pump_loop(self) -> None:
        """The registerForExecution analogue: while host work is pending,
        step the device; otherwise park on the wake event. A step failure
        must not kill the pump (outstanding asks would hang with no timeout
        enforcement) — it is reported and the loop continues."""
        while not self._shutdown:
            try:
                self._pump_once()
            except Exception:  # noqa: BLE001 — pump must survive
                import traceback
                traceback.print_exc()
                # timeout enforcement lives in _resolve_waiters: on a
                # persistently failing step, outstanding asks must still
                # time out rather than hang their callers forever
                try:
                    self._resolve_waiters()
                except Exception:  # noqa: BLE001
                    pass
                time.sleep(0.5)

    def _enqueue_step(self, inflight: deque) -> None:
        """Dispatch ONE fused flush+step program and hand its attention
        handle to the pipeline. The step lock covers only the enqueue —
        tell/ask staging overlaps device execution, which is the point of
        the depth-k pump. Adaptive coalescing falls out: tells staged
        while older programs execute all ride the NEXT enqueue's flush as
        one program instead of one flush per pump iteration."""
        with self._step_lock:
            rt = self._runtime  # re-resolve: rebuild swaps under this lock
            self._pending_tells = 0  # this step's flush drains all staged
            rt.step()
            inflight.append(rt.attention)
        self._stat_steps += 1
        self._maybe_checkpoint()

    def _drain_one(self, inflight: deque) -> int:
        """Retire the OLDEST in-flight program: fetch its [ATT_WORDS]
        attention word — the device_get doubles as that program's sync,
        since the word is a non-donated output — and run only the host
        work its bits call for. Returns the flag word."""
        att = np.asarray(jax.device_get(inflight.popleft()))
        self._stat_drains += 1
        for s, phi, det in self._sentinel.observe(att):
            if s not in self._sentinel_reported:
                self._sentinel_reported.add(s)
                if self.flight_recorder is not None:
                    self.flight_recorder.device_suspected(
                        "bridge", shard=int(s), phi=float(phi), detector=det)
        flags = int(att.reshape(-1)[ATT_FLAGS])
        self._service(flags)
        return flags

    def _service(self, flags: int) -> None:
        """Post-drain host work, gated on the attention bits: the wide
        promise-block readback and the failed-row scan only run when
        their bit says there is something to read."""
        if flags & ATT_LATCH_BIT:
            self._stat_wide_resolves += 1
            self._resolve_waiters()
        elif self._waiters or self._promise_zombies:
            self._stat_host_checks += 1
            self._check_waiters_host()
        if flags & ATT_FAILED_BIT:
            self._handle_failures()
        elif self._reported_failed:
            self._reported_failed.clear()

    def _check_waiters_host(self) -> None:
        """Deadline bookkeeping with ZERO device reads — the no-latch
        drain path. Mirrors _resolve_waiters' not-done branch exactly:
        start first-visit timeout clocks, fire expired asks into
        quarantine, and reap zombies past their hard deadline (their late
        reply never latched, or ATT_LATCH_BIT would have routed this
        drain to the wide path)."""
        now = time.monotonic()
        with self._lock:
            waiting = list(self._waiters.items())
        for prow, (fut, _c) in waiting:
            deadline, timeout = self._waiter_deadlines.get(prow, (now, 0.0))
            if deadline is None:
                # first post-step visit: start the timeout clock now
                with self._lock:
                    if prow in self._waiter_deadlines:
                        self._waiter_deadlines[prow] = (now + timeout,
                                                        timeout)
                continue
            if now <= deadline:
                continue
            with self._lock:
                if self._waiters.pop(prow, None) is None:
                    continue  # another resolver claimed it
                _, timeout = self._waiter_deadlines.pop(prow, (0.0, 0.0))
                self._promise_zombies[prow] = now + max(5.0 * timeout, 30.0)
            if not fut.done():
                from ..pattern.ask import AskTimeoutException
                fut.set_exception(AskTimeoutException(
                    f"device ask timed out after [{timeout}s]"))
        with self._lock:
            for prow, kill_at in list(self._promise_zombies.items()):
                if now > kill_at:
                    del self._promise_zombies[prow]
                    self._promise_free.append(prow - self._promise_base)

    def _pump_once(self) -> None:
        depth = self.pipeline_depth
        inflight: deque = deque()  # attention-word handles, oldest first
        while not self._shutdown:
            if self._has_pending():
                self._ensure_runtime()
                self._enqueue_step(inflight)
                while len(inflight) >= depth:
                    self._drain_one(inflight)
                if self._waiters and not self._fresh_tells():
                    # an outstanding ask with nothing newly staged: the
                    # reply needs more device steps (multi-hop) or will
                    # never come. Drain eagerly so a latched reply
                    # resolves NOW — depth-k must not add pipeline
                    # latency to the ask path — then pace the freewheel
                    # (interruptibly: a fresh tell/ask cuts the wait)
                    while inflight:
                        self._drain_one(inflight)
                    if self._waiters and self.auto_step_interval > 0:
                        self._pump_wake.wait(self.auto_step_interval)
                        self._pump_wake.clear()
                continue
            if inflight:
                # pending work exhausted: retire the tail — each drain
                # may resolve waiters or surface failures, which re-raises
                # _has_pending and loops back to the busy path
                self._drain_one(inflight)
                continue
            # busy->idle edge: the device-slab drain point (epoch-gated —
            # one scalar fetch when nothing accumulated) and the pipeline
            # delta report share it, so the depth-k pipeline never pays a
            # mid-flight sync for telemetry
            self.drain_metrics()
            fr = self.flight_recorder
            if fr is not None and fr.enabled:
                self._report_pipeline(fr)  # busy->idle edge: emit deltas
            self._pump_wake.wait(timeout=0.05)
            self._pump_wake.clear()
            if self._promise_zombies and not self._shutdown:
                # quarantined timed-out slots: step at a LOW cadence (their
                # late replies free the slots; a flat-out step loop would
                # burn the device for the whole quarantine window). The
                # interruptible wait lets fresh asks/tells wake us early.
                self._pump_wake.wait(timeout=0.25)
                self._pump_wake.clear()
                if self._has_pending():
                    continue  # fresh work takes the fast path above
                self._ensure_runtime()
                self._enqueue_step(inflight)
                # full service ON THIS DRAIN: failures surfacing during
                # quarantine-cadence steps are restarted/reported here,
                # not deferred to the next busy iteration
                self._drain_one(inflight)

    def step(self, n: int = 1, depth: Optional[int] = None) -> None:
        """Explicit stepping for benches/tests (pump-free driving), as a
        depth-k pipeline: up to `depth` (default: the handle's
        pipeline_depth) fused flush+step programs stay in flight, so
        tells staged while older programs execute coalesce into the next
        enqueue's flush. Synchronous at return — all n steps have
        completed and waiters/failures were serviced. Depth changes
        overlap, never results: the depth-1 vs depth-k bit-parity tests
        pin that equivalence."""
        self._ensure_runtime()
        d = self.pipeline_depth if depth is None else max(1, int(depth))
        inflight: deque = deque()
        for _ in range(n):
            self._enqueue_step(inflight)
            while len(inflight) >= d:
                self._drain_one(inflight)
        while inflight:
            self._drain_one(inflight)
        # explicit stepping is synchronous at return — a quiescent point,
        # so it doubles as a drain point like the pump's busy->idle edge
        self.drain_metrics()

    # ------------------------------------------------- checkpoint / recovery
    def checkpoint(self, directory: Optional[str] = None) -> str:
        """Checkpoint barrier: drain the depth-k pipeline to a quiescent
        point, then snapshot the complete slab pytree. Holding the step
        lock stops new enqueues; BatchedSystem.checkpoint's
        block_until_ready (a host read of the non-donated step_count) then
        retires every already-dispatched program. Attention handles the
        pump still holds stay valid across the barrier — they are
        non-donated outputs, so the pipeline resumes where it left off.
        The write-ahead journal compacts to records at/after the snapshot
        step. Returns the snapshot path."""
        d = directory or self.checkpoint_dir
        if d is None:
            raise ValueError(
                "no checkpoint directory: pass one or configure "
                "checkpoint-dir on the dispatcher")
        self._ensure_runtime()
        t0 = time.perf_counter()
        with self._step_lock:
            rt = self._runtime  # re-resolve: rebuild swaps under this lock
            path = rt.checkpoint(d, keep=self.checkpoint_keep)
            step = rt._host_step
        elapsed = time.perf_counter() - t0
        size = 0
        try:
            if os.path.isdir(path):
                for root, _dirs, files in os.walk(path):
                    size += sum(os.path.getsize(os.path.join(root, f))
                                for f in files)
            else:
                size = os.path.getsize(path)
        except OSError:
            pass
        st = self._ckpt_stats
        st["checkpoints"] += 1
        st["last_step"] = step
        st["last_duration_s"] = round(elapsed, 6)
        st["last_size_bytes"] = int(size)
        st["last_path"] = path
        fr = self.flight_recorder
        if fr is not None and fr.enabled:
            fr.device_checkpoint("batched", step, elapsed, int(size), path)
        # checkpoint barrier = the other slab drain point: the pipeline is
        # already quiesced, so the full fetch costs no extra sync
        self.drain_metrics()
        return path

    def restore(self, path: Optional[str] = None) -> int:
        """Recovery: rebuild device state from a snapshot (default: the
        newest in checkpoint_dir), replay the write-ahead journal to the
        crash frontier, and fail every outstanding ask with
        RecoveredAskLost — promise latch state does not survive the
        snapshot overwrite, so their replies can never arrive and hanging
        the waiters until timeout would be strictly worse. All promise
        slots return to the free list with their latches lowered. Returns
        the recovered host step counter."""
        if path is None and self.checkpoint_dir is None:
            raise ValueError("no checkpoint directory configured")
        self._ensure_runtime()
        with self._step_lock:
            if path is None:
                # resolve INSIDE the step lock: the pump's auto-checkpoint
                # both writes newer snapshots and compacts the journal past
                # them — a path resolved outside the lock could go stale
                # while a concurrent checkpoint drops exactly the journal
                # records the stale snapshot's replay needs
                from ..persistence.slab_snapshot import latest_slab_path
                path = latest_slab_path(self.checkpoint_dir)
                if path is None:
                    raise FileNotFoundError(
                        f"no snapshot under {self.checkpoint_dir}")
            rt = self._runtime  # re-resolve: rebuild swaps under this lock
            with self._lock:
                orphaned = list(self._waiters.items())
                self._waiters.clear()
                self._waiter_deadlines.clear()
                self._promise_zombies.clear()
                self._promise_free = list(range(self.promise_rows_n))
            for prow, (fut, _c) in orphaned:
                if not fut.done():
                    fut.set_exception(RecoveredAskLost(
                        f"ask on promise row {prow} was outstanding when "
                        f"the runtime restored from {path}; its reply "
                        f"cannot be recovered"))
            step = rt.restore(path, journal=self._journal)
            # lower EVERY promise latch: the snapshot may carry a latched
            # pre-crash reply whose asker was just failed above — a stale
            # latch would complete the slot's NEXT ask with the previous
            # question's answer
            base = self._promise_base
            if base is not None:
                col = rt.state[self.PROMISE_REPLIED]
                rt.state[self.PROMISE_REPLIED] = \
                    col.at[base:base + self.promise_rows_n].set(False)
            self._pending_tells = 0
            self._reported_failed.clear()
        self._wake_pump()  # replayed frontier tells may be staged
        return step

    def _maybe_checkpoint(self) -> None:
        """Auto-cadence hook on the enqueue path (pump and explicit
        step() both land here): snapshot every checkpoint_interval_steps
        dispatched steps. Snapshot-IO failures DEGRADE to keep-running:
        the circuit breaker stops hammering a sick filesystem, the
        exponential-backoff gate paces retries, and the only symptom is a
        checkpoint_failed flight-recorder warning — the step loop never
        stalls (ISSUE 4 tentpole #4)."""
        if self.checkpoint_interval_steps <= 0 or self.checkpoint_dir is None:
            return
        if self._stat_steps - self._ckpt_last_step < \
                self.checkpoint_interval_steps:
            return
        now = time.monotonic()
        if now < self._ckpt_retry_at:
            return
        self._ckpt_last_step = self._stat_steps
        try:
            self._ckpt_breaker.with_sync_circuit_breaker(self.checkpoint)
            self._ckpt_failures = 0
        except CircuitBreakerOpenException as e:
            # open breaker: skip quietly until it half-opens
            self._ckpt_retry_at = now + max(float(e.remaining), 0.1)
        except Exception as e:  # noqa: BLE001 — degrade, never stall
            self._ckpt_failures += 1
            self._ckpt_stats["failures"] += 1
            self._ckpt_retry_at = now + backoff_delay(
                self._ckpt_failures, 0.5, 30.0)
            fr = self.flight_recorder
            if fr is not None and fr.enabled:
                fr.checkpoint_failed("batched", repr(e)[:200],
                                     self._ckpt_failures)

    def checkpoint_stats(self) -> Dict[str, Any]:
        """Checkpoint cadence counters (watchdog artifact + tests):
        snapshots taken/failed, last duration/size/step/path."""
        return dict(self._ckpt_stats)

    def pipeline_stats(self) -> Dict[str, Any]:
        """Pipeline telemetry: configured depth, programs enqueued/drained,
        how many drains paid the wide promise readback vs host-only
        deadline checks, and the runtime's dispatch percentiles (the span
        around each launch, `host_stats()`)."""
        host = self.host_stats()
        return {"depth": self.pipeline_depth,
                "steps": self._stat_steps,
                "drains": self._stat_drains,
                "wide_resolves": self._stat_wide_resolves,
                "host_checks": self._stat_host_checks,
                "dispatch_p50_us": host.get("dispatch_us_p50", 0.0),
                "dispatch_p99_us": host.get("dispatch_us_p99", 0.0)}

    def host_stats(self) -> Dict[str, Any]:
        """The runtime's host side (`BatchedSystem.host_stats()`):
        dispatches and their percentiles, `starved`, compiles. Empty before
        the runtime is built; a rebuilt runtime counts anew."""
        rt = self._runtime
        return rt.host_stats() if rt is not None else {}

    def ask_pool_stats(self) -> Dict[str, Any]:
        """Promise-pool occupancy: the admission signal for ask traffic.
        `in_flight` counts claimed slots (waiters + quarantined zombies),
        `exhausted` the typed AskPoolExhausted fast-fails so far, and
        `occupancy` the claimed fraction — the gateway sheds above a
        threshold on this BEFORE asks start fast-failing."""
        with self._lock:
            free = len(self._promise_free)
            zombies = len(self._promise_zombies)
            waiting = len(self._waiters)
            exhausted = self._stat_ask_exhausted
        size = self.promise_rows_n
        in_flight = max(0, size - free)
        return {"size": size, "free": free, "in_flight": in_flight,
                "waiting": waiting, "zombies": zombies,
                "exhausted": exhausted,
                "occupancy": (in_flight / size) if size else 1.0}

    def sentinel_stats(self) -> Dict[str, Any]:
        """Detection-lane telemetry: drains observed, shards currently
        suspected (the device behind this handle is shard 0), and the
        failover budget carried for parity with MeshSentinel."""
        return {"drains": self._sentinel.drains,
                "suspected": sorted(self._sentinel.suspected()),
                "max_failovers": self.sentinel_max_failovers,
                "depth_recovery_rounds": self.sentinel_depth_recovery_rounds}

    def _sentinel_metrics(self) -> Dict[str, Any]:
        """sentinel_stats plus the numeric gauges the registry surfaces:
        suspicion count and the phi value of shard 0 (this handle's only
        shard) — the detector's continuous health signal, not just the
        tripped/untripped bit."""
        st = self.sentinel_stats()
        st["suspected_count"] = len(st.pop("suspected", ()))
        try:
            st["phi"] = float(self._sentinel.phi(0))
        except Exception:  # noqa: BLE001 — phi before first heartbeat
            st["phi"] = 0.0
        return st

    def drain_metrics(self) -> None:
        """Conditional device-slab drain into the registry. The quiet path
        costs ONE scalar fetch (the epoch word); a changed epoch pays the
        [N_HIST, N_BUCKETS] slab fetch and re-ingests. Host stats ride
        along via the registered collectors at exposition time, so this
        only moves device data. Called at the pump's busy->idle edge, the
        checkpoint barrier, and explicit step() returns."""
        reg = self.metrics_registry
        if reg is None or not self.metrics_enabled:
            return
        with self._step_lock:  # a drain must not race a fresh enqueue
            rt = self._runtime
            if rt is None:
                return
            drained = rt.drain_metrics()
            host_step = rt._host_step
        if drained is not None:
            step, lanes = drained
            reg.ingest_device_slab(lanes, step)
        else:
            reg.set_step(host_step)

    def _report_pipeline(self, fr) -> None:
        """Emit pipeline counter DELTAS as a device_pipeline event (same
        snapshot pattern as BatchedSystem._report_supervision); called on
        the pump's busy->idle edge and at shutdown, not per drain."""
        totals = np.asarray([self._stat_steps, self._stat_drains,
                             self._stat_wide_resolves,
                             self._stat_host_checks], np.int64)
        delta = totals - self._stat_reported
        if not delta.any():
            return
        self._stat_reported = totals
        fr.device_pipeline("batched", self.pipeline_depth, int(delta[0]),
                           int(delta[1]), int(delta[2]), int(delta[3]))

    def _handle_failures(self) -> None:
        """Host-mediated supervision of device error lanes: rows that set
        `_failed` are restarted with reset state (default), stopped, or
        left suspended, per failure_policy; each failure is published ONCE
        (suspended rows keep the flag by design and must not re-report)."""
        rt = self._runtime
        if rt is None or "_failed" not in rt.state:
            return
        with self._step_lock:
            rt = self._runtime
            if not rt.any_failed():  # one device scalar on the hot path
                if self._reported_failed:
                    self._reported_failed.clear()
                return
            failed = rt.failed_rows()
            current = set(int(r) for r in failed)
            new = current - self._reported_failed
            if self.failure_policy == "restart":
                rt.restart_rows(failed)
                # restore spawn-time init values for the restarted rows
                # (an Akka restart re-instantiates from Props); per-row
                # array inits are sliced to the failed positions so values
                # stay aligned with their rows
                for rows, init in self._spawn_inits:
                    pos = np.nonzero(np.isin(rows, failed))[0]
                    if pos.size:
                        hit = jnp.asarray(rows[pos])
                        for col, value in init.items():
                            v = _slice_init(value, pos, rows.size)
                            rt.state[col] = rt.state[col].at[hit].set(
                                jnp.asarray(v, rt.state[col].dtype))
                self._reported_failed.clear()
            elif self.failure_policy == "stop":
                rt.stop_block(failed)
                rt.clear_failed(failed)  # a dead row must not re-report
                self._reported_failed.clear()
            else:  # suspend: flag stays (that IS the suspension)
                self._reported_failed = current
        if not new:
            return
        new_arr = np.asarray(sorted(new), np.int32)
        es = self.event_stream
        if es is not None:
            es.publish(DeviceActorFailed(new_arr, self.failure_policy))
        fr = self.flight_recorder
        if fr is not None and fr.enabled:
            for r in new_arr[:64]:
                fr.actor_failed(f"device-row-{int(r)}", "error-lane")

    def shutdown(self) -> None:
        self._shutdown = True
        self._pump_wake.set()
        t = self._pump_thread
        if t is not None:
            t.join(timeout=2.0)
        fr = self.flight_recorder
        if fr is not None and fr.enabled:
            self._report_pipeline(fr)  # flush the final pipeline deltas
        try:
            self.drain_metrics()  # final slab frame before sinks close
        except Exception:  # noqa: BLE001 — shutdown must not raise
            pass
        if self._owns_registry and self.metrics_registry is not None:
            self.metrics_registry.close()
        if self._journal is not None:
            self._journal.close()


class DeviceActorFailed:
    """EventStream notification: device rows raised their `_failed` error
    lane and were handled per the handle's failure_policy (host-mediated
    supervision — FaultHandling.scala parity for the batched runtime)."""

    __slots__ = ("rows", "action")

    def __init__(self, rows, action: str):
        self.rows = rows
        self.action = action

    def __repr__(self):
        return f"DeviceActorFailed(rows={list(self.rows)!r}, action={self.action})"


class DroppedDeviceMessages:
    """EventStream notification: host tells dropped on inbox overflow
    (bounded-mailbox dead-letter visibility, dispatch/Mailbox.scala:415-443)."""

    __slots__ = ("count",)

    def __init__(self, count: int):
        self.count = count

    def __repr__(self):
        return f"DroppedDeviceMessages({self.count})"


class DeviceDeadLetters:
    """EventStream notification: tells dead-lettered because their pinned
    incarnation generation no longer matches the row (the target was stopped
    — and possibly respawned — after the ref was captured; uid-in-path
    parity, ActorCell.scala:382-388)."""

    __slots__ = ("count",)

    def __init__(self, count: int):
        self.count = count

    def __repr__(self):
        return f"DeviceDeadLetters({self.count})"


# ------------------------------------------------------------------- the refs
class DeviceActorRef(InternalActorRef):
    """An ActorRef whose mailbox is a device row. Watchable; tells after stop
    go to dead letters (FunctionRef-pattern bookkeeping). The ref pins the
    row's incarnation GENERATION at creation (the reference's uid-in-path,
    ActorCell.scala:382-388): a tell through a stale ref — the row was
    stopped and the slot respawned — dead-letters instead of reaching the
    new occupant. `supervisor` is the parent that spawned the ref through
    Props: like any child it tells the parent when it has stopped, or a
    terminating parent would wait for it forever."""

    __slots__ = ("path", "_handle", "row", "gen", "_codec", "_system",
                 "_stopped", "_watched_by", "_wlock", "_supervisor")

    def __init__(self, system, handle: BatchedRuntimeHandle, row: int, path,
                 codec: Optional[MessageCodec] = None, gen=None,
                 supervisor: Optional[InternalActorRef] = None):
        self.path = path
        self._system = system
        self._supervisor = supervisor
        self._handle = handle
        self.row = int(row)
        self.gen = (int(gen) if gen is not None
                    else int(handle.generation_of(row)[0]))
        self._codec = codec
        self._stopped = False
        self._watched_by: set = set()
        self._wlock = threading.Lock()

    def tell(self, message: Any, sender: Optional[ActorRef] = None) -> None:
        if self._stopped:
            self._system.dead_letters.tell(
                DeadLetter(message, sender, self), sender)
            return
        self._handle.tell(self.row, message, self._codec, expect_gen=self.gen)

    def ask(self, message: Any, timeout: float = 5.0) -> Future:
        return self._handle.ask(self.row, message, timeout, self._codec,
                                expect_gen=self.gen)

    def ask_sync(self, message: Any, timeout: float = 5.0) -> Any:
        return self.ask(message, timeout).result(timeout + 1.0)

    def read_state(self, col: str) -> np.ndarray:
        return self._handle.read_state(col, np.asarray([self.row]))[0]

    def send_system_message(self, message: sysmsg.SystemMessage) -> None:
        if isinstance(message, sysmsg.Watch):
            with self._wlock:
                if self._stopped:
                    message.watcher.send_system_message(
                        sysmsg.DeathWatchNotification(
                            self, existence_confirmed=True))
                else:
                    self._watched_by.add(message.watcher)
        elif isinstance(message, sysmsg.Unwatch):
            with self._wlock:
                self._watched_by.discard(message.watcher)

    def stop(self) -> None:
        with self._wlock:
            if self._stopped:
                return
            self._stopped = True
            watchers = list(self._watched_by)
            self._watched_by.clear()
        self._handle.stop_rows([self.row])
        if self._supervisor is not None:
            watchers.append(self._supervisor)
        for w in watchers:
            w.send_system_message(
                sysmsg.DeathWatchNotification(self, existence_confirmed=True))

    @property
    def is_terminated(self) -> bool:
        return self._stopped


class DeviceBlockRef(InternalActorRef):
    """One ref for a spawned block of device actors. `tell` broadcasts to
    every row (the bulk path — one staged batch, not n Python calls);
    `block[i]` derives the per-row ref. `supervisor`: as on DeviceActorRef."""

    __slots__ = ("path", "_handle", "rows", "gens", "_codec", "_system",
                 "_supervisor", "_stopped")

    def __init__(self, system, handle: BatchedRuntimeHandle, rows: np.ndarray,
                 path, codec: Optional[MessageCodec] = None,
                 supervisor: Optional[InternalActorRef] = None):
        self.path = path
        self._system = system
        self._supervisor = supervisor
        self._stopped = False
        self._handle = handle
        self.rows = rows
        self.gens = handle.generation_of(rows)  # pinned incarnations
        self._codec = codec

    def __len__(self) -> int:
        return len(self.rows)

    def __getitem__(self, i: int) -> DeviceActorRef:
        return DeviceActorRef(self._system, self._handle, self.rows[i],
                              self.path / str(i), self._codec,
                              gen=self.gens[i])

    def tell(self, message: Any, sender: Optional[ActorRef] = None) -> None:
        self._handle.tell_rows(self.rows, message, self._codec,
                               expect_gen=self.gens)

    def read_state(self, col: str) -> np.ndarray:
        return self._handle.read_state(col, self.rows)

    def stop(self) -> None:
        if self._stopped:
            return
        self._stopped = True
        self._handle.stop_rows(self.rows)
        if self._supervisor is not None:
            self._supervisor.send_system_message(
                sysmsg.DeathWatchNotification(self, existence_confirmed=True))


# ----------------------------------------------------------------- device props
class DeviceSpec:
    """Attached to Props to mark a device actor (the deploy-info analogue,
    actor/Deployer.scala)."""

    __slots__ = ("behavior", "n", "init_state", "codec")

    def __init__(self, behavior: BatchedBehavior, n: int = 1,
                 init_state: Optional[Dict[str, Any]] = None,
                 codec: Optional[MessageCodec] = None):
        self.behavior = behavior
        self.n = n
        self.init_state = init_state
        self.codec = codec


def device_props(b: BatchedBehavior, n: int = 1,
                 init_state: Optional[Dict[str, Any]] = None,
                 codec: Optional[MessageCodec] = None,
                 dispatcher: Optional[str] = None):
    """Props for a device-resident actor (block). Spawn with
    system.actor_of(device_props(my_behavior), "name")."""
    from ..actor.props import Props
    return Props(factory=_no_factory, cls=None, dispatcher=dispatcher,
                 device=DeviceSpec(b, n, init_state, codec))


def _no_factory():  # pragma: no cover — device props never build a host actor
    raise RuntimeError("device props have no host-side actor factory")


def get_handle(system, dispatcher_id: Optional[str] = None) -> BatchedRuntimeHandle:
    """The dispatcher-owned device runtime handle for a system (bench/test
    access)."""
    from ..dispatch.batched import TpuBatchedDispatcher
    did = dispatcher_id or system.dispatchers.DEFAULT_DISPATCHER_ID
    disp = system.dispatchers.lookup(did)
    if not isinstance(disp, TpuBatchedDispatcher):
        # fall back to the dedicated device dispatcher id
        disp = system.dispatchers.lookup("akka.actor.tpu-dispatcher")
    return disp.handle(system)
