"""BatchedSystem: the SoA device runtime — millions of actors per chip.

This is the `tpu-batched` Dispatcher/Mailbox of the BASELINE north star. The
mapping from the reference's hot path (SURVEY.md §3.2):

  reference                                   here
  ---------                                   ----
  ActorRef.! -> mailbox.enqueue               tell() -> host staging buffer, or
    (dispatch/Dispatcher.scala:61-65)           on-device Emit from a behavior
  registerForExecution CAS + thread pool      the step loop itself (jit)
    (dispatch/Dispatcher.scala:120-143)
  Mailbox.processMailbox dequeue loop         reduce mode: segment reduction;
    (dispatch/Mailbox.scala:260-277)            slots mode: rank-then-scatter
                                                ordered delivery — a narrow
                                                key-only sort ranks messages
                                                per (recipient, seq), then
                                                closed-form scatters place
                                                them into per-actor mailbox
                                                slots (ordered, per-message —
                                                the full envelope-mailbox
                                                contract; ops/segment.py)
  ActorCell.invoke -> receive                 vmapped behavior switch
    (actor/ActorCell.scala:539-555)             (lax.switch over behavior ids)

State is a dict of [capacity, ...] columns (union of all behavior schemas);
messages are (dst, type, payload, valid) SoA blocks; one `step` delivers every
in-flight message and runs every live actor's update, entirely on device.
`run(n)` lax.scans the step so multi-step benches never touch the host.

`delivery` names the reduce kernel ("auto" | "scatter" | "merge").
`delivery_backend` (constructor arg, forwarded to ops/segment.py) names the
kernel family of slots delivery and, on a mesh, of the exchange bucketing:
None/"auto" picks by platform, "xla" forces rank-then-scatter, "reference"
forces the wide-sort kernels a TPU runs — bit-identical in results, so a CPU
test can run the chip's side (see docs/DELIVERY_KERNELS.md).
"""

from __future__ import annotations

import os
import threading
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .behavior import BatchedBehavior
from .host_side import HostSide, build_span
from .metrics_slab import (ASK_ARM_COL, ASK_ARM_SPEC, accumulate_step,
                           empty_slab, slab_dict)
from .step import StepCore
from .supervision import (ATT_WORDS, N_COUNTERS, SUP_COLUMNS, counts_dict,
                          decode_attention, reserved_fill)

# the lanes of `spill_stats` past its two where a slots behavior answers
EMIT_STATS = ("stashed", "stash_high_water", "stash_overflow",
              "retained_by_budget", "emit_high_water")
EMIT_SUMS = ("stashed", "stash_overflow", "retained_by_budget")


def drive_pipelined(step_once: Callable[[], None],
                    latest_handle: Callable[[], Any],
                    n_steps: int, depth: int,
                    on_drain: Optional[Callable[[np.ndarray], None]] = None,
                    ) -> None:
    """Shared enqueue-ahead driver (BatchedSystem and ShardedBatchedSystem
    run_pipelined): dispatch up to `depth` single-step programs before
    blocking on the oldest, keyed off each dispatch's attention-word
    handle. With `on_drain`, every retired program's word is fetched
    (device_get — the sync) and handed to the callback, and the tail is
    fully drained before returning so no word is skipped; without it the
    tail stays in flight and the caller picks its own sync point."""
    if depth < 1:
        raise ValueError("depth must be >= 1")
    from collections import deque
    inflight: deque = deque()  # attention-word handles, oldest first

    def drain_one() -> None:
        h = inflight.popleft()
        if on_drain is None:
            jax.block_until_ready(h)
        else:
            on_drain(np.asarray(jax.device_get(h)))

    for _ in range(n_steps):
        step_once()
        inflight.append(latest_handle())
        while len(inflight) >= depth:
            drain_one()
    while on_drain is not None and inflight:
        drain_one()


class BatchedSystem:
    """Single-device (or single-shard) batched actor space.

    capacity: max live actors (rows); out_degree K: max emissions per actor per
    step; payload_width P: message payload columns; host_inbox: slots reserved
    for host-injected tells per flush; mailbox_slots S: 0 = commutative
    reduction inboxes (fast path), >0 = per-message mailboxes of S ordered
    (type, payload) slots per actor (full Akka mailbox semantics — required
    when any behavior declares inbox="slots").
    """

    @build_span
    def __init__(self, capacity: int, behaviors: Sequence[BatchedBehavior],
                 payload_width: int = 4, out_degree: int = 1,
                 host_inbox: int = 1024, payload_dtype=jnp.float32,
                 device: Optional[Any] = None, delivery: str = "auto",
                 need_max: bool = False, topology=None,
                 mailbox_slots: int = 0,
                 native_staging: Optional[bool] = None,
                 spill_capacity: Optional[int] = None,
                 delivery_backend: Optional[str] = None,
                 attention_latch_col: Optional[str] = None,
                 metrics_enabled: bool = False,
                 routers: Sequence[Any] = (),
                 emit_rows: Optional[int] = None):
        if not behaviors:
            raise ValueError("at least one behavior required")
        # the host side's one bracket: spans, compile rows, host_stats()
        self._host = HostSide("batched")
        self.capacity = int(capacity)
        self.behaviors = list(behaviors)
        self.payload_width = int(payload_width)
        self.out_degree = int(out_degree)
        self.host_inbox = int(host_inbox)
        self.payload_dtype = payload_dtype
        self.device = device
        self.delivery = delivery
        # ops/segment.py kernel-implementation seam: None/"auto" = platform
        # cost model, "xla" = rank-then-scatter, "reference" = wide sorts
        self.delivery_backend = delivery_backend
        self.need_max = need_max
        self.topology = topology  # ops.segment.StaticTopology | None
        self.mailbox_slots = int(mailbox_slots)
        if self.mailbox_slots == 0 and any(b.inbox == "slots" for b in behaviors):
            # a slots behavior present => the whole system steps in slots mode
            self.mailbox_slots = max(2, self.out_degree)
        # slots mode defaults to UNBOUNDED mailbox semantics (the reference's
        # default, dispatch/Mailbox.scala:647): overflow past the S slots and
        # suspended-row mail ride a spill region at the FRONT of the inbox
        # and redeliver next step in FIFO order. spill_capacity=0 opts into
        # bounded-mailbox drop-and-count semantics.
        if self.mailbox_slots > 0:
            self.spill_cap = (int(spill_capacity) if spill_capacity is not None
                              else max(self.host_inbox, 4 * self.mailbox_slots))
        else:
            self.spill_cap = 0
        # a slots behavior that answers (`emit_width`, behavior.py): rows
        # [0, emit_rows) take that many emission rows each, the others
        # `out_degree`; without one every row takes `out_degree`
        self.emit_width = max((b.emit_width for b in self.behaviors),
                              default=0)
        self.emit_rows = (min(int(emit_rows), self.capacity)
                          if emit_rows is not None and self.emit_width
                          else self.capacity)
        width = self.emit_width or self.out_degree
        self.emission_rows = (self.emit_rows * width
                              + (self.capacity - self.emit_rows)
                              * self.out_degree)

        # unified state schema (union of behavior columns; conflicting specs are errors)
        self.state_spec: Dict[str, Tuple[Tuple[int, ...], Any]] = {}
        for b in self.behaviors:
            for col, spec in b.state_spec.items():
                if col in self.state_spec and self.state_spec[col] != spec:
                    raise ValueError(
                        f"behavior {b.name}: state column {col!r} conflicts "
                        f"({self.state_spec[col]} vs {spec})")
                self.state_spec[col] = ((tuple(spec[0])), spec[1])
        # pool routers (routing/batched.py BatchedRouter): each is a row of
        # this table, spawned with the pool's own behavior, whose `next` and
        # `routed` columns the step's route stage keeps (StepCore.route)
        self.routers = tuple(routers)
        for pool in self.routers:
            if pool.behavior not in self.behaviors:
                raise ValueError(
                    f"router at row {pool.row}: its behavior "
                    f"{pool.behavior.name!r} is not among the system's")
            last = pool.routee_base + pool.n_routees
            if not (0 <= pool.row < self.capacity
                    and 0 <= pool.routee_base and last <= self.capacity):
                raise ValueError(
                    f"router at row {pool.row} with routees "
                    f"[{pool.routee_base}, {last}) does not fit "
                    f"{self.capacity} rows")
        # in-graph supervision bookkeeping (batched/supervision.py): any
        # supervised behavior pulls in the full column set; a bare
        # nonfinite_guard only needs the error lane itself
        if any(getattr(b, "supervisor", None) is not None for b in behaviors):
            for col, spec in SUP_COLUMNS.items():
                self.state_spec.setdefault(col, spec)
        elif any(getattr(b, "nonfinite_guard", False) for b in behaviors):
            self.state_spec.setdefault("_failed", SUP_COLUMNS["_failed"])
        # in-graph metric slab (batched/metrics_slab.py): the ask-latency
        # lane needs the arm-step column the bridge stamps in ask() — only
        # meaningful when a promise latch column exists at all
        self.metrics_on = bool(metrics_enabled)
        if self.metrics_on and attention_latch_col is not None:
            self.state_spec.setdefault(ASK_ARM_COL, ASK_ARM_SPEC)

        n = self.capacity
        self.state: Dict[str, jax.Array] = {
            k: jnp.zeros((n,) + shape, dtype=dtype)
            for k, (shape, dtype) in self.state_spec.items()}
        for col in self.state:  # _become/_restart_at re-arm to -1, not 0
            if reserved_fill(col):
                self.state[col] = jnp.full_like(self.state[col],
                                                reserved_fill(col))
        self.behavior_id = jnp.zeros((n,), dtype=jnp.int32)
        self.alive = jnp.zeros((n,), dtype=jnp.bool_)
        self.step_count = jnp.asarray(0, jnp.int32)
        self.mail_dropped = jnp.asarray(0, jnp.int32)  # mailbox-slot overflow
        # [spilled, spill_high_water]: messages the spill region carried
        # over to the next step, summed over the steps run (int32, modulo
        # 2^32), and the most it held after any one step; zeros where the
        # system has no spill region
        # Where a slots behavior answers, five lanes follow
        # (`EMIT_STATS`): stashed, stash_high_water, stash_overflow,
        # retained_by_budget, emit_high_water
        self.spill_stats = jnp.zeros(
            (2 + (len(EMIT_STATS) if self.emit_width else 0),), jnp.int32)
        # aggregate supervision counters (supervision.COUNTER_NAMES order),
        # accumulated in-graph — reading them is the host's choice, never
        # forced on the step path
        self.sup_counts = jnp.zeros((N_COUNTERS,), jnp.int32)
        self._sup_reported = np.zeros((N_COUNTERS,), np.int64)  # FR snapshot
        # host-attention word (supervision.pack_attention): [ATT_WORDS]
        # int32 summary emitted as an extra NON-donated output of every
        # step — the depth-k pipelined drivers sync on THIS handle and
        # read the flag bits instead of wide per-column device_gets
        self.attention = jnp.zeros((ATT_WORDS,), jnp.int32)
        # in-graph metric slab ([N_HIST, N_BUCKETS] int32 histograms,
        # batched/metrics_slab.py) riding the carry like sup_counts, and
        # its epoch word — a non-donated scalar output (sum of the slab,
        # the attention-word trick) the host polls to decide whether a
        # full slab drain is worth fetching. The slab rides the carry even
        # when metrics are off (static carry structure; XLA aliases the
        # untouched buffer through), but all stamping/accumulation is
        # gated out at TRACE time by metrics_on.
        self.metrics = empty_slab()
        self.metrics_epoch = jnp.asarray(0, jnp.int32)

        # inbox layout: [spill_cap | n*K emissions | host_inbox] — spill
        # first so redelivered (older) mail outranks fresh emissions in the
        # stable (recipient, seq) delivery sort. With an emission width the
        # emissions are [emit_rows * width | the other rows * K]
        m = self.spill_cap + self.emission_rows + self.host_inbox
        self.inbox_dst = jnp.full((m,), -1, dtype=jnp.int32)
        self.inbox_type = jnp.zeros((m,), dtype=jnp.int32)
        self.inbox_payload = jnp.zeros((m, self.payload_width), dtype=payload_dtype)
        self.inbox_valid = jnp.zeros((m,), dtype=jnp.bool_)
        # enqueue-step column for the sojourn-age lane: the step a row was
        # written (emissions: the writing step; host flush: the flushing
        # dispatch; spill re-injection re-stamps). (0,) when metrics are
        # off — the column costs nothing unless measured.
        self.inbox_enq = jnp.zeros((m,) if self.metrics_on else (0,),
                                   jnp.int32)

        self._next_row = 0
        self._free_rows: List[int] = []
        self._host_staged: List[Tuple[int, int, np.ndarray]] = []
        self._lock = threading.Lock()
        self._dropped_host = 0  # guarded by _lock; stager drops counted natively
        # per-row incarnation counter (the reference's path uid,
        # ActorCell.scala:382-388): bumped on stop, checked by tells that
        # carry expect_gen — a tell aimed at a dead incarnation dead-letters
        # instead of reaching the row's next occupant. Host-authoritative:
        # generations only change on the host (spawn/stop are slow-path),
        # so a host-side check at stage time is exact.
        self._generation = np.zeros((n,), np.int64)
        self.dead_lettered = 0  # generation-mismatch tells (guarded by _lock)
        self.on_dead_letter: Optional[Callable[[int], None]] = None
        # overflow visibility hook (bounded-mailbox dead-letter parity,
        # dispatch/Mailbox.scala:415-443): the dispatcher bridge wires this
        # to the EventStream so host_inbox overflow surfaces as Dropped
        self.on_dropped: Optional[Callable[[int], None]] = None
        # optional FlightRecorder (event/flight_recorder.py SPI): step/flush
        # events for post-mortem traces; None = zero overhead
        self.flight_recorder = None
        # (mailbox_overflow, exchange_dropped) high-water marks already
        # surfaced as shard_overflow warnings — counters are cumulative,
        # warn only on growth
        self._overflow_reported = (0, 0)
        # host mirror of the dispatched-step counter: incremented when a
        # step is DISPATCHED (device step_count lags until sync). The WAL
        # tags each staged batch with this counter — a batch staged at c is
        # flushed by dispatch c+1, which is what replay reproduces.
        self._host_step = 0
        # optional write-ahead journal (persistence/tell_journal.py):
        # tell/seed_inbox append the staged batch BEFORE enqueue
        self.tell_journal = None
        # native staging buffer: producers memcpy rows into a preallocated
        # C++ buffer with one atomic reserve, the flush drains a contiguous
        # block (SURVEY.md §2.10 item 5 — envelope-pool parity). Rows carry
        # [type:4bytes][payload] so typed tells ride the same memcpy. Opt-out
        # (the Python staging list) via native_staging=False or
        # AKKA_TPU_NATIVE=0; otherwise a stager that cannot be built raises.
        self._stager = None
        self._np_payload_dtype = np.dtype(jnp.dtype(payload_dtype))
        if self.mailbox_slots > 0 and self._np_payload_dtype.itemsize != 4:
            # the stager's type column is a bitcast into payload bytes,
            # exact only for 4-byte dtypes; narrower dtypes (bf16/f16) would
            # round type tags — use the exact Python staging path instead
            native_staging = False
        if native_staging is not False and \
                os.environ.get("AKKA_TPU_NATIVE", "1") != "0":
            from ..native.queues import NativeStager
            # slots mode: one extra leading column carries the message
            # type, bitcast into the payload dtype's bytes (4-byte
            # dtypes roundtrip exactly). Reduce mode stages bare
            # payloads — no per-tell cost for a column delivery ignores.
            extra = 1 if self.mailbox_slots > 0 else 0
            self._stager = NativeStager(
                self.host_inbox, self.payload_width + extra,
                self._np_payload_dtype)

        # shape-stable flush: [host_inbox]-shaped host pads + ONE jitted
        # update program (a per-batch-size .at[idx].set would recompile for
        # every distinct staged count)
        self._flush_jit = jax.jit(self._flush_impl,
                                  donate_argnums=(0, 1, 2, 3, 4))
        # fused flush+step: ONE program dispatch when host tells are staged
        # (the tell->receive latency path pays per-dispatch overhead twice
        # otherwise)
        self._flush_step_jit = jax.jit(self._flush_step_impl,
                                       donate_argnums=tuple(range(12)))

        self._core = StepCore(self.behaviors, n_local=self.capacity,
                              payload_width=self.payload_width,
                              out_degree=self.out_degree,
                              payload_dtype=payload_dtype,
                              slots=self.mailbox_slots, need_max=need_max,
                              topology=topology, delivery=delivery,
                              spill_cap=self.spill_cap,
                              delivery_backend=delivery_backend,
                              attention_latch_col=attention_latch_col,
                              routers=self.routers,
                              emit_rows=self.emit_rows)
        # the emitting path's counters as the host last read them
        self._emit_seen: Dict[str, int] = {}
        # host cache of the last INGESTED metrics epoch (the registry's
        # drain bookkeeping rides here so rebuilds carry it over)
        self._metrics_seen_epoch = 0

        # topology tables ride as runtime arguments (pytree): closure
        # constants would be baked into the HLO (multi-MB programs break
        # remote compile). Kind/scalars are trace-time constants.
        self._topo_arrays = topology.runtime_arrays() if topology is not None else ()
        donate = tuple(range(12))  # everything but step_count
        self._step_jit = jax.jit(self._step_impl, donate_argnums=donate)
        self._run_jit = jax.jit(self._run_impl, static_argnums=(13,),
                                donate_argnums=donate)

    # ------------------------------------------------------------- lifecycle
    def spawn_block(self, behavior: BatchedBehavior | int, n: int,
                    init_state: Optional[Dict[str, Any]] = None) -> np.ndarray:
        """Allocate n actors with the given behavior. Host-side slow path,
        mirroring the reference's spawn being off the message hot loop.
        Fresh capacity is handed out contiguously; once the tail is
        exhausted, rows freed by stop_block are REUSED (free-list churn —
        SURVEY.md §7 hard parts: spawn/stop via free-lists). Reused rows
        get zeroed state and their stale inbox slots scrubbed. Incarnation
        identity is guarded by the per-row generation counter (the
        reference's path uid, ActorCell.scala:382-388): capture it with
        `generation_of(ids)` and pass `expect_gen` to tell() — a tell
        raced against stop+respawn of the same row then dead-letters
        instead of reaching the new occupant (stop bumps the generation;
        the stage-time check plus this method's scrub of staged/in-flight
        messages closes the window). Returns the global ids."""
        b_idx = behavior if isinstance(behavior, int) else self.behaviors.index(behavior)
        answers = self.behaviors[b_idx].emit_width > 0
        with self._lock:
            start = self._next_row
            fresh = min(n, self.capacity - start)
            reused = n - fresh
            if reused > len(self._free_rows):
                raise RuntimeError(
                    f"actor capacity exhausted ({n} requested, "
                    f"{self.capacity - start} fresh + "
                    f"{len(self._free_rows)} free)")
            self._next_row = start + fresh
            recycled: List[int] = []
            if reused:
                recycled = sorted(self._free_rows[-reused:])
                del self._free_rows[-reused:]
        ids = np.concatenate([
            np.arange(start, start + fresh, dtype=np.int32),
            np.asarray(recycled, dtype=np.int32)]) if reused else \
            np.arange(start, start + fresh, dtype=np.int32)
        if answers and ids.size and int(ids.max()) >= self.emit_rows:
            with self._lock:  # give the rows back before refusing
                self._next_row = start
                self._free_rows.extend(recycled)
            raise ValueError(
                f"behavior {self.behaviors[b_idx].name!r} answers: its rows "
                f"stand below emit_rows ({self.emit_rows}), row "
                f"{int(ids.max())} does not")
        idx = jnp.asarray(ids)
        self.behavior_id = self.behavior_id.at[idx].set(b_idx)
        self.alive = self.alive.at[idx].set(True)
        self._core.note_rows(b_idx, ids)
        if reused:
            # a recycled row must start life fresh: zero every state column
            # (reserved cols get their re-arm values) and scrub any stale
            # in-flight messages addressed to it — BOTH the device inbox
            # and the not-yet-flushed host staging queues (a tell staged
            # against the old occupant must never reach the new one)
            rec_arr = np.asarray(recycled, np.int32)
            ridx = jnp.asarray(rec_arr)
            for col, arr in self.state.items():
                self.state[col] = arr.at[ridx].set(
                    jnp.asarray(reserved_fill(col), arr.dtype))
            stale = jnp.isin(self.inbox_dst, ridx)
            self.inbox_valid = jnp.where(stale, False, self.inbox_valid)
            if self._stager is not None:
                # drain + filter + re-stage. Caveat: a producer staging
                # concurrently can interleave ahead of re-staged (older)
                # messages — spawn-into-recycled-rows is a slow path and
                # same-sender interleaving requires that sender to race its
                # own spawn. Short counts are real drops and are reported.
                d, r = self._stager.drain()
                if d.shape[0]:
                    keep = ~np.isin(d, rec_arr)
                    if keep.any():
                        staged = self._stager.stage(
                            np.ascontiguousarray(d[keep]),
                            np.ascontiguousarray(r[keep]))
                        n_lost = int(keep.sum()) - staged
                        if n_lost > 0 and self.on_dropped is not None:
                            self.on_dropped(n_lost)
            with self._lock:
                rec_set = set(int(i) for i in rec_arr)
                self._host_staged = [e for e in self._host_staged
                                     if e[0] not in rec_set]
        if init_state:
            for col, value in init_state.items():
                if col not in self.state:
                    raise KeyError(f"unknown state column {col!r}")
                self.state[col] = self.state[col].at[idx].set(
                    jnp.asarray(value, dtype=self.state[col].dtype))
        return ids

    def stop_block(self, ids: np.ndarray) -> None:
        """Mark actors dead and recycle their rows (their rows stop
        updating and emitting; capacity is reclaimed for future spawns).
        Bumps the rows' incarnation generation so stale expect_gen tells
        dead-letter (ActorCell.scala:382-388 uid parity)."""
        arr = np.unique(np.atleast_1d(np.asarray(ids, np.int32)))
        self.alive = self.alive.at[jnp.asarray(arr)].set(False)
        with self._lock:
            self._generation[arr] += 1
            seen = set(self._free_rows)
            self._free_rows.extend(int(i) for i in arr if int(i) not in seen)

    def generation_of(self, ids) -> np.ndarray:
        """Current incarnation generation of the given rows (capture at
        spawn; pass to tell(expect_gen=...) to pin the incarnation)."""
        arr = np.atleast_1d(np.asarray(ids, np.int64))
        with self._lock:
            return self._generation[arr].copy()

    # ------------------------------------------------------------------ tell
    def tell(self, dst, payload, mtype: int = 0, expect_gen=None) -> None:
        """Host-side tell: staged, flushed into the inbox on next step.
        dst: int or [k] array; payload: [P] or [k, P]; mtype: message-type
        tag (int or [k] array) delivered in slots mode. expect_gen (int or
        [k] array): the sender's captured incarnation generation — a
        mismatch (the row was stopped, possibly respawned, since capture)
        dead-letters the message instead of delivering it to the wrong
        occupant (path-uid parity, ActorCell.scala:382-388)."""
        dst_arr = np.atleast_1d(np.asarray(dst, dtype=np.int32))
        if expect_gen is not None:
            gens = np.broadcast_to(
                np.atleast_1d(np.asarray(expect_gen, np.int64)),
                dst_arr.shape)
            with self._lock:
                ok = self._generation[dst_arr] == gens
            if not ok.all():
                n_dead = int((~ok).sum())
                with self._lock:
                    self.dead_lettered += n_dead
                if self.on_dead_letter is not None:
                    self.on_dead_letter(n_dead)
                if not ok.any():
                    return
                dst_arr = dst_arr[ok]
                payload = np.asarray(payload, dtype=self._np_payload_dtype)
                if payload.ndim > 1:
                    payload = payload[ok]
                if np.ndim(mtype) > 0:
                    mtype = np.asarray(mtype, np.int32)[ok]
        pl = np.asarray(payload, dtype=self._np_payload_dtype)
        if pl.ndim == 1:
            # broadcast a single payload row to every destination — the
            # native stager memcpys k full rows, so the buffer must hold k
            pl = np.broadcast_to(pl[None, :],
                                 (dst_arr.shape[0], pl.shape[0]))
        if pl.shape[-1] != self.payload_width:
            pad = self.payload_width - pl.shape[-1]
            if pad < 0:
                raise ValueError(f"payload wider than {self.payload_width}")
            pl = np.pad(pl, [(0, 0)] * (pl.ndim - 1) + [(0, pad)])
        mt = np.broadcast_to(np.atleast_1d(np.asarray(mtype, np.int32)),
                             (dst_arr.shape[0],))
        if self.tell_journal is not None:
            # WAL: journal the normalized, generation-filtered batch BEFORE
            # it reaches any staging buffer — recovery re-stages exactly
            # this batch at this step counter, no expect_gen re-check
            self.tell_journal.append(self._host_step, "tell", dst_arr, pl, mt)
        if self._stager is not None:
            if self.mailbox_slots > 0:
                rows = np.empty((dst_arr.shape[0], self.payload_width + 1),
                                self._np_payload_dtype)
                rows[:, 0] = self._pack_type(mt)
                rows[:, 1:] = pl
            else:
                rows = pl
            staged = self._stager.stage(dst_arr, rows)
            if staged < dst_arr.shape[0] and self.on_dropped is not None:
                self.on_dropped(dst_arr.shape[0] - staged)
            return
        with self._lock:
            for d, t, p in zip(dst_arr, mt, pl):
                self._host_staged.append((int(d), int(t), p))

    def _pack_type(self, mt: np.ndarray) -> np.ndarray:
        """int32 type tags -> one payload-dtype column (bitcast when the
        dtype is 4 bytes — exact roundtrip; value cast otherwise)."""
        if self._np_payload_dtype.itemsize == 4:
            return mt.astype(np.int32).view(self._np_payload_dtype)
        return mt.astype(self._np_payload_dtype)

    def _unpack_type(self, col: np.ndarray) -> np.ndarray:
        if self._np_payload_dtype.itemsize == 4:
            return np.ascontiguousarray(col).view(np.int32)
        return col.astype(np.int32)

    def seed_inbox(self, dst, payload, mtype=0) -> None:
        """Bulk device-side injection: overwrite the first len(dst) inbox slots
        (the fast path for benches / bulk tells — the equivalent of the
        reference bench pre-filling mailboxes, TellOnlyBenchmark.scala:19-92)."""
        if self.tell_journal is not None:
            # seeds write device slots directly, so a seed record at the
            # snapshot's own step may already be IN the snapshot — replay
            # overwrites the same slots with the same values (idempotent)
            self.tell_journal.append(self._host_step, "seed",
                                     np.asarray(dst), np.asarray(payload),
                                     np.asarray(mtype))
        dst = jnp.asarray(dst, jnp.int32)
        payload = jnp.asarray(payload, self.payload_dtype)
        if payload.ndim == 1:
            payload = jnp.broadcast_to(payload[None, :], (dst.shape[0], self.payload_width))
        k = dst.shape[0]
        if k > self.inbox_dst.shape[0]:
            raise ValueError("seed exceeds inbox capacity")
        mt = jnp.broadcast_to(jnp.asarray(mtype, jnp.int32), (k,))
        self.inbox_dst = self.inbox_dst.at[:k].set(dst)
        self.inbox_type = self.inbox_type.at[:k].set(mt)
        self.inbox_payload = self.inbox_payload.at[:k].set(payload)
        self.inbox_valid = self.inbox_valid.at[:k].set(True)

    def _flush_impl(self, inbox_dst, inbox_type, inbox_payload, inbox_valid,
                    inbox_enq, dsts, mts, pls, valid, step_count):
        """One static-shape program: overwrite the host region of the inbox.
        [host_inbox]-shaped args regardless of how many tells are staged.
        With metrics on, flushed rows stamp the enqueue-step column with
        the flushing dispatch's counter — delivered by that same dispatch
        (fused flush+step) their sojourn age reads 0."""
        base = self.spill_cap + self.emission_rows
        upd = jax.lax.dynamic_update_slice
        if self.metrics_on:
            stamp = jnp.broadcast_to(jnp.asarray(step_count, jnp.int32),
                                     (self.host_inbox,))
            inbox_enq = upd(inbox_enq, stamp, (base,))
        return (upd(inbox_dst, dsts, (base,)),
                upd(inbox_type, mts, (base,)),
                upd(inbox_payload, pls, (base, 0)),
                upd(inbox_valid, valid, (base,)),
                inbox_enq)

    def _new_pads(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray]:
        """Empty host pads (dst, type, payload, valid) for ONE flush
        dispatch. Every dispatch owns the pads it is given and nothing
        writes them again: jnp.asarray may alias a numpy buffer (zero-copy
        on CPU whenever the allocation is 64-byte aligned), and both
        dispatch and the host-to-device copy are asynchronous, so a pad
        refilled by the next drain could change under a program that has
        not read it yet."""
        return (np.full((self.host_inbox,), -1, np.int32),
                np.zeros((self.host_inbox,), np.int32),
                np.zeros((self.host_inbox, self.payload_width),
                         self._np_payload_dtype),
                np.zeros((self.host_inbox,), np.bool_))

    def _pads_to_device(self, pads) -> Tuple[jax.Array, ...]:
        dsts, mts, pls, valid = pads
        return (jnp.asarray(dsts), jnp.asarray(mts),
                jnp.asarray(pls, self.payload_dtype), jnp.asarray(valid))

    def _run_flush(self, pads) -> None:
        """Dispatch the flush program over pads filled by _drain_to_pad."""
        (self.inbox_dst, self.inbox_type, self.inbox_payload,
         self.inbox_valid, self.inbox_enq) = self._flush_jit(
            self.inbox_dst, self.inbox_type, self.inbox_payload,
            self.inbox_valid, self.inbox_enq,
            *self._pads_to_device(pads), self.step_count)

    def _flush_step_impl(self, state, behavior_id, alive, inbox_dst,
                         inbox_type, inbox_payload, inbox_valid, inbox_enq,
                         mail_dropped, sup_counts, metrics, spill_stats,
                         step_count, dsts, mts, pls, valid, topo_arrays=()):
        """flush + step as ONE program (the latency hot path)."""
        (inbox_dst, inbox_type, inbox_payload, inbox_valid,
         inbox_enq) = self._flush_impl(
            inbox_dst, inbox_type, inbox_payload, inbox_valid, inbox_enq,
            dsts, mts, pls, valid, step_count)
        return self._step_impl(state, behavior_id, alive, inbox_dst,
                               inbox_type, inbox_payload, inbox_valid,
                               inbox_enq, mail_dropped, sup_counts, metrics,
                               spill_stats, step_count, topo_arrays)

    def _drain_to_pad(self):
        """Drain staged host tells (native stager or Python list) into
        fresh pads (`_new_pads`), applying overflow-drop accounting.
        Returns (k, pads): the number of staged rows and the pads for this
        dispatch, or (0, None) when there is nothing to flush."""
        if self._stager is not None:
            dsts_np, rows_np = self._stager.drain()
            k = dsts_np.shape[0]
            if k == 0:
                return 0, None
            pads = self._new_pads()
            dsts, mts, pls, valid = pads
            dsts[:k] = dsts_np
            if self.mailbox_slots > 0:
                mts[:k] = self._unpack_type(rows_np[:, 0])
                pls[:k] = rows_np[:, 1:]
            else:
                pls[:k] = rows_np
        else:
            with self._lock:
                staged, self._host_staged = self._host_staged, []
            if not staged:
                return 0, None
            if len(staged) > self.host_inbox:
                n_drop = len(staged) - self.host_inbox
                with self._lock:
                    self._dropped_host += n_drop
                if self.on_dropped is not None:
                    self.on_dropped(n_drop)
                staged = staged[: self.host_inbox]
            k = len(staged)
            pads = self._new_pads()
            dsts, mts, pls, valid = pads
            dsts[:k] = [d for d, _, _ in staged]
            mts[:k] = [t for _, t, _ in staged]
            pls[:k] = np.stack([p for _, _, p in staged])
        valid[:k] = True
        return k, pads

    def _flush_staged(self) -> None:
        k, pads = self._drain_to_pad()
        if k == 0:
            return
        self._run_flush(pads)
        if self.flight_recorder is not None:
            self.flight_recorder.device_flush("batched", k)

    # ------------------------------------------------------------------ step
    def _step_impl(self, state, behavior_id, alive, inbox_dst, inbox_type,
                   inbox_payload, inbox_valid, inbox_enq, mail_dropped,
                   sup_counts, metrics, spill_stats, step_count,
                   topo_arrays=()):
        sc = self.spill_cap
        nk = self.emission_rows
        old_alive = alive
        (new_state, behavior_id, alive, emits, dropped, spill,
         sup_delta, dcount) = self._core.run_local(
            state, behavior_id, alive, inbox_dst, inbox_type, inbox_payload,
            inbox_valid, step_count, topo_arrays)
        new_metrics = metrics
        if self.metrics_on:
            with jax.named_scope("akka.metrics"):
                new_metrics = accumulate_step(
                    metrics, state, new_state, old_alive, dcount,
                    inbox_valid, inbox_enq, step_count,
                    latch_col=self._core.attention_latch_col)

        # write emissions in place over the donated inbox buffers (rows
        # [sc, sc+n*K) are exactly the emission slots; retained spill goes
        # FIRST; host rows are cleared) — no per-step concatenate/realloc
        # (VERDICT r1 weak #2)
        with jax.named_scope("akka.emit"):
            if self.emit_width:
                with jax.named_scope("akka.emit.slots"):
                    counts = emits.counts
                    emits, sent, lost = self._core.pack_emits(emits.emit)
                    dropped = dropped + lost
            out_dst = emits.dst.reshape(-1)
            # behaviors may compute emissions in a wider dtype (f32 math on a
            # bf16 wire): value-cast onto the system payload dtype, the same
            # contract host tells follow
            out_payload = emits.payload.reshape(
                -1, self.payload_width).astype(inbox_payload.dtype)
            out_valid = emits.valid.reshape(-1)
            upd = jax.lax.dynamic_update_slice
            new_inbox_dst = upd(inbox_dst, out_dst, (sc,)).at[sc + nk:].set(-1)
            if self.mailbox_slots > 0:
                out_type = emits.type.reshape(-1)
                new_inbox_type = upd(inbox_type, out_type,
                                     (sc,)).at[sc + nk:].set(0)
            else:
                new_inbox_type = inbox_type  # never read in reduce mode
            new_inbox_payload = upd(inbox_payload, out_payload,
                                    (sc, 0)).at[sc + nk:].set(0)
            new_inbox_valid = upd(inbox_valid, out_valid,
                                  (sc,)).at[sc + nk:].set(False)
            new_inbox_enq = inbox_enq
            if self.metrics_on:
                # emissions written this step carry this step's counter (their
                # delivery next step reads age 1); retained spill is RE-stamped
                # at injection, so sojourn ages count steps since last
                # (re)stamp — per-source semantics, docs/OBSERVABILITY.md
                stamp = jnp.broadcast_to(jnp.asarray(step_count, jnp.int32),
                                         (nk,))
                new_inbox_enq = upd(inbox_enq, stamp,
                                    (sc,)).at[sc + nk:].set(0)
                if sc > 0:
                    new_inbox_enq = new_inbox_enq.at[:sc].set(
                        jnp.asarray(step_count, jnp.int32))
            if spill is not None:  # spill is None iff sc == 0
                with jax.named_scope("akka.emit.spill"):
                    sp_dst, sp_type, sp_pl, sp_v = spill
                    new_inbox_dst = new_inbox_dst.at[:sc].set(sp_dst)
                    new_inbox_type = new_inbox_type.at[:sc].set(sp_type)
                    new_inbox_payload = new_inbox_payload.at[:sc].set(sp_pl)
                    new_inbox_valid = new_inbox_valid.at[:sc].set(sp_v)
                    carried = jnp.sum(sp_v.astype(jnp.int32))
                    grown = [spill_stats[0] + carried,
                             jnp.maximum(spill_stats[1], carried)]
                    if self.emit_width:
                        grown += self._core.emit_lanes(
                            lambda i: spill_stats[2 + i], counts, new_state,
                            sent)
                    spill_stats = jnp.stack(grown)
        new_dropped = mail_dropped + dropped
        new_counts = sup_counts + sup_delta
        # the attention word and the metrics epoch are pure functions of
        # the new carry, appended as outputs OUTSIDE the donation set
        # (indices 0-10): their buffers are never aliased, so device_get
        # on them is a safe sync
        attention = self._core.attention_word(new_state, new_dropped,
                                              new_counts, step_count + 1)
        epoch = (jnp.sum(new_metrics).astype(jnp.int32) if self.metrics_on
                 else jnp.asarray(0, jnp.int32))
        return (new_state, behavior_id, alive, new_inbox_dst, new_inbox_type,
                new_inbox_payload, new_inbox_valid, new_inbox_enq,
                new_dropped, new_counts, new_metrics, spill_stats,
                step_count + 1, attention, epoch)

    def _run_impl(self, state, behavior_id, alive, inbox_dst, inbox_type,
                  inbox_payload, inbox_valid, inbox_enq, mail_dropped,
                  sup_counts, metrics, spill_stats, step_count, n_steps: int,
                  topo_arrays=()):
        def body(carry, _):
            # drop the per-step attention word and metrics epoch inside the
            # scan: every field is carry-derived (flags = current state,
            # counters and the slab cumulative), so recomputing them once
            # from the final carry loses nothing
            return self._step_impl(*carry, topo_arrays)[:13], None

        carry = (state, behavior_id, alive, inbox_dst, inbox_type,
                 inbox_payload, inbox_valid, inbox_enq, mail_dropped,
                 sup_counts, metrics, spill_stats, step_count)
        carry, _ = jax.lax.scan(body, carry, None, length=n_steps)
        attention = self._core.attention_word(carry[0], carry[8], carry[9],
                                              carry[12])
        epoch = (jnp.sum(carry[10]).astype(jnp.int32) if self.metrics_on
                 else jnp.asarray(0, jnp.int32))
        return carry + (attention, epoch)

    def _carry(self):
        return (self.state, self.behavior_id, self.alive, self.inbox_dst,
                self.inbox_type, self.inbox_payload, self.inbox_valid,
                self.inbox_enq, self.mail_dropped, self.sup_counts,
                self.metrics, self.spill_stats, self.step_count)

    def _set_carry(self, out) -> None:
        # `out` is a step/run output: the 13 carry slots plus the
        # non-donated attention word and metrics epoch
        (self.state, self.behavior_id, self.alive, self.inbox_dst,
         self.inbox_type, self.inbox_payload, self.inbox_valid,
         self.inbox_enq, self.mail_dropped, self.sup_counts, self.metrics,
         self.spill_stats, self.step_count, self.attention,
         self.metrics_epoch) = out

    def step(self) -> None:
        """One delivery+update step. Staged host tells ride INSIDE the same
        program dispatch (the fused flush+step program) — half the per-step
        overhead of flush-then-step on the tell→receive latency path."""
        # host-side; excluded from dispatch timing
        k, pads = self._drain_to_pad()
        fr = self.flight_recorder
        if fr is not None and k > 0:
            fr.device_flush("batched", k)
        with self._host.dispatch("akka.device.step", self, 1):
            if k > 0:
                self._set_carry(self._flush_step_jit(
                    *self._carry(), *self._pads_to_device(pads),
                    self._topo_arrays))
            else:
                self._set_carry(self._step_jit(*self._carry(),
                                               self._topo_arrays))
        self._host_step += 1
        if fr is not None:
            self._report_supervision(fr)

    def run(self, n_steps: int) -> None:
        """n steps fully on device (lax.scan) — the bench hot loop."""
        self._flush_staged()
        with self._host.dispatch(f"akka.device.run[{n_steps}]", self,
                                 n_steps):
            self._set_carry(self._run_jit(*self._carry(), n_steps,
                                          self._topo_arrays))
        self._host_step += int(n_steps)
        if self.flight_recorder is not None:
            self._report_supervision(self.flight_recorder)

    def run_pipelined(self, n_steps: int, depth: int = 2,
                      on_attention: Optional[Callable[[Dict[str, Any]],
                                                      None]] = None) -> None:
        """n SEPARATE single-step dispatches with up to `depth` programs in
        flight: step k+1 is enqueued before step k completes, hiding host
        program-launch latency behind device execution. Donation makes the
        hand-off free — each dispatch consumes the previous dispatch's
        not-yet-materialized outputs, so the host never syncs inside the
        window (Artery's enqueue/flush decoupling,
        Association.scala:330-395, as a step driver).

        Unlike run(), host tells staged BETWEEN dispatches ride in the
        next step (run() fuses the whole window into one program that
        flushes once) — this is the latency-oriented driver, run() the
        throughput-oriented one.

        The pipeline keys off each step's host-attention word (not
        step_count): with `on_attention`, every retired step's decoded
        word (supervision.decode_attention) is delivered in order and the
        tail is fully drained before returning — the narrow-readback hook
        the bridge pump builds on."""
        cb = None
        if on_attention is not None:
            cb = lambda w: on_attention(decode_attention(w))  # noqa: E731
        drive_pipelined(lambda: self.step(), lambda: self.attention,
                        n_steps, depth, on_drain=cb)

    def warmup(self) -> None:
        """Execute the step AND the flush once on throwaway zero-filled
        buffers so the REAL first step — and any ask waiting on it — doesn't
        absorb the cold XLA compile. Executed rather than
        lower().compile()'d: an AOT compile leaves the executable cached
        but the first real call still traces and lowers again. The clones
        are donated and freed; our live carry is untouched."""
        def free(out):
            jax.tree.map(
                lambda a: a.delete() if hasattr(a, "delete") else None, out)

        with self._host.warmup(self):
            pads = self._pads_to_device(self._new_pads())
            clone = jax.tree.map(jnp.zeros_like, self._carry())
            free(self._step_jit(*clone, self._topo_arrays))
            m = self.inbox_dst.shape[0]
            free(self._flush_jit(
                jnp.zeros((m,), jnp.int32), jnp.zeros((m,), jnp.int32),
                jnp.zeros((m, self.payload_width), self.payload_dtype),
                jnp.zeros((m,), jnp.bool_),
                jnp.zeros_like(self.inbox_enq),
                *pads, jnp.asarray(0, jnp.int32)))
            clone = jax.tree.map(jnp.zeros_like, self._carry())
            free(self._flush_step_jit(*clone, *pads, self._topo_arrays))

    def block_until_ready(self) -> None:
        self._host.wait(self.step_count)

    def host_stats(self) -> Dict[str, Any]:
        """The host side of this driver (HostSide.host_stats): dispatches
        and their percentiles, `starved`, compiles; and `slots_extent`,
        the slots extent the last step program traced took (StepCore;
        None before one, or in reduce mode)."""
        return {**self._host.host_stats(),
                "slots_extent": self._core.compiled_extent,
                **self._emit_seen}

    def read_attention(self) -> Dict[str, int]:
        """Decode the newest host-attention word — one tiny device_get
        that (like block_until_ready) also syncs the newest dispatched
        step, since the word is a non-donated output of that program."""
        word = decode_attention(self.attention)
        fr = self.flight_recorder
        if fr is not None:
            # single device = shard 0: same shard_overflow warning the
            # sharded runtime localizes per mesh row
            mail = int(word.get("mail_dropped", 0))
            exch = int(word.get("exchange_dropped", 0))
            seen_mail, seen_exch = self._overflow_reported
            if mail > seen_mail or exch > seen_exch:
                fr.shard_overflow("batched", shard=0, mailbox_overflow=mail,
                                  dropped=exch)
                self._overflow_reported = (mail, exch)
        return word

    # ---------------------------------------------------- in-graph metrics
    def metrics_epoch_value(self) -> int:
        """One tiny device_get of the non-donated metrics-epoch word —
        like read_attention it doubles as a sync for the newest dispatched
        step. Cheap enough for the pump's busy→idle edge to poll."""
        return int(np.asarray(jax.device_get(self.metrics_epoch)))

    def read_metrics(self) -> Dict[str, np.ndarray]:
        """Host copy of the metric slab as named [N_BUCKETS] int64 lanes
        (metrics_slab.HIST_NAMES; per-shard slab rows summed). Implicitly
        drains the dispatch pipeline (see read_state)."""
        self.block_until_ready()
        return slab_dict(self.metrics)

    def drain_metrics(self):
        """Epoch-gated slab drain for the bridge/registry: returns
        (step, {name: [N_BUCKETS] int64}) when the slab grew since the
        last drain, else None. The quiet path costs ONE scalar device_get
        (the epoch word) — no slab fetch, no extra sync beyond the one
        the caller's drain point already implies."""
        if not self.metrics_on:
            return None
        with self._host.read("metrics"):
            epoch = self.metrics_epoch_value()
            if epoch == self._metrics_seen_epoch:
                return None
            self._metrics_seen_epoch = epoch
            step = int(np.asarray(jax.device_get(self.step_count)))
            lanes = slab_dict(self.metrics)
            if self.routers:
                # beside the delivery counts: what each pool has routed so far
                lanes["routed"] = np.asarray(
                    [r["routed"] for r in self.read_routers()], np.int64)
            if self.spill_cap > 0:
                lanes["spilled"], lanes["spill_high_water"] = (
                    np.asarray([v], np.int64) for v in self.read_spill())
            if self.emit_width:
                for name, v in self.read_emit_stats().items():
                    lanes[name] = np.asarray([v], np.int64)
            return step, lanes

    def read_spill(self) -> Tuple[int, int]:
        """(`spilled`, `spill_high_water`): the messages the spill region
        carried over to a next step, summed over every step run (the int32
        counter read modulo 2^32), and the most it held after any one step.
        What passes `spill_capacity` in one step is not carried but lost,
        and `mailbox_overflow` counts it."""
        with self._host.read("spill"):
            self.block_until_ready()
            spilled, high = np.asarray(jax.device_get(self.spill_stats))[:2]
        return int(spilled) % (1 << 32), int(high)

    def read_emit_stats(self) -> Dict[str, int]:
        """The emitting path's counters (`EMIT_STATS`), where a slots
        behavior declares an emission width (else {}): `stashed`, messages
        put in a stash, `stash_overflow`, those a full stash could not take
        (lost: counted here and nowhere else), and
        `retained_by_budget`, mailbox messages an emission budget left for
        the next step, summed over the steps run (int32 counters read
        modulo 2^32); `stash_high_water`, the most one stash held after a
        step, and `emit_high_water`, the most messages one row sent in a
        step."""
        if not self.emit_width:
            return {}
        with self._host.read("spill"):
            self.block_until_ready()
            lanes = np.asarray(jax.device_get(self.spill_stats))[2:]
        out = {name: int(v) % (1 << 32) if name in EMIT_SUMS else int(v)
               for name, v in zip(EMIT_STATS, lanes)}
        self._emit_seen = dict(out)
        return out

    def read_routers(self) -> List[Dict[str, int]]:
        """Each pool router's row with its counters as the route stage
        keeps them: `next`, the sequence number the next message takes
        (modulo the pool's size), and `routed`, the messages routed so far
        (the int32 column read modulo 2^32)."""
        if not self.routers:
            return []
        rows = np.asarray([pool.row for pool in self.routers])
        with self._host.read("routers"):
            nxt, routed = [self.read_state(col, rows)
                           for col in ("next", "routed")]
        return [{"row": int(r), "next": int(n),
                 "routed": int(c) % (1 << 32)}
                for r, n, c in zip(rows, nxt, routed)]

    # ------------------------------------------------- checkpoint / recovery
    def checkpoint(self, directory: str, keep: Optional[int] = None) -> str:
        """Checkpoint barrier: drain every in-flight dispatch to a
        quiescent point (a host read of the non-donated step_count — the
        pipeline's safe sync handle), then snapshot the complete schema-v2
        slab pytree (state columns incl. supervision slabs, inbox tensors,
        aggregate counters, attention word). With a write-ahead tell
        journal attached, the journal is compacted to records at/after the
        snapshot step; `keep` bounds retained snapshots (oldest GC'd).
        Returns the snapshot path."""
        from ..persistence.slab_snapshot import gc_slabs, save_slabs
        self.block_until_ready()
        path = save_slabs(self, directory)
        if self.tell_journal is not None:
            self.tell_journal.compact(self._host_step)
        if keep is not None:
            gc_slabs(directory, keep)
        return path

    def restore(self, path: str, journal=None) -> int:
        """Crash recovery: load a snapshot (schema v1 or v2) into this
        system and reset the host step counter from its step_count. The
        caller builds a same-config system and re-runs its spawns first —
        behaviors are code, not snapshot data, so host allocation state
        (row free-list, generations) is rebuilt by the spawn replay, then
        the device slabs are overwritten here. Host staging buffers are
        discarded: anything staged-but-unflushed at the crash replays from
        the journal, never from stale buffers. With `journal` set,
        journaled batches past the snapshot step are replayed to the crash
        frontier. Returns the restored host step counter."""
        from ..persistence.slab_snapshot import restore_slabs
        from ..persistence.tell_journal import replay_journal
        restore_slabs(self, path)
        self._host_step = int(np.asarray(jax.device_get(self.step_count)))
        # re-arm the drain gate against the RESTORED slab: seen resets to 0
        # and the epoch handle (normally a step output) is recomputed from
        # the slab, so a restored non-empty slab is drainable immediately,
        # not only after the first post-restore run
        self.metrics_epoch = jnp.asarray(
            int(np.asarray(jax.device_get(self.metrics)).sum()), jnp.int32)
        self._metrics_seen_epoch = 0
        if self._stager is not None:
            self._stager.drain()
        with self._lock:
            self._host_staged = []
        if journal is not None:
            replay_journal(self, journal)
        return self._host_step

    # -------------------------------------------------------- fault handling
    def any_failed(self) -> bool:
        """One device scalar — the pump's cheap per-tick check."""
        from .step import fault_any_failed
        return fault_any_failed(self.state)

    def failed_rows(self) -> np.ndarray:
        """Rows whose behavior raised the `_failed` flag (error lanes —
        suspended until restarted; FaultHandling.scala parity).

        Implicitly drains the dispatch pipeline first: with run_pipelined
        steps in flight, the state slabs are donated/aliased buffers that
        some platforms report ready early — host reads must sync on the
        non-donated step_count before touching them."""
        from .step import fault_failed_rows
        self.block_until_ready()
        return fault_failed_rows(self.state)

    def restart_rows(self, ids,
                     init_state: Optional[Dict[str, Any]] = None) -> None:
        """Host-mediated restart-with-reset-state: zero the rows' state
        (reserved columns re-armed), clear the failure flag, keep the
        behavior (preRestart/postRestart with a fresh instance —
        ActorCell.scala:589-602 faultRecreate analogue). A restart is a
        NEW incarnation: the rows' generation bumps, so a tell whose
        expect_gen was captured before the restart dead-letters instead
        of reaching the restarted occupant (path-uid parity with
        stop_block)."""
        from .step import fault_restart_rows
        self.state = fault_restart_rows(self.state, ids, init_state)
        arr = np.unique(np.atleast_1d(np.asarray(ids, np.int32)))
        with self._lock:
            self._generation[arr] += 1

    def clear_failed(self, ids) -> None:
        from .step import fault_clear_failed
        self.state = fault_clear_failed(self.state, ids)

    # ---------------------------------------------- in-graph supervision
    @property
    def supervision_counts(self) -> Dict[str, int]:
        """Aggregate in-graph supervision counters (failed/resumed/
        restarted/stopped/escalated/dead_letters) accumulated by the jitted
        step. Reading is a host read of 6 int32s — the host's choice of
        sync point, never forced on the step path."""
        return counts_dict(self.sup_counts)

    def any_escalated(self) -> bool:
        """ONE device scalar: did any supervised lane escalate? The cheap
        aggregate check the host polls at ITS cadence (the escalation
        analogue of any_failed)."""
        if "_escalated" not in self.state:
            return False
        return bool(jax.device_get(jnp.any(self.state["_escalated"])))

    def escalated_rows(self) -> np.ndarray:
        """Rows whose supervisor escalated (suspended, awaiting host
        resolution via restart_rows/clear_failed/stop_block)."""
        if "_escalated" not in self.state:
            return np.empty((0,), np.int32)
        flags = np.asarray(jax.device_get(self.state["_escalated"]))
        return np.nonzero(flags)[0].astype(np.int32)

    def _report_supervision(self, fr) -> None:
        """Emit the supervision-counter DELTA since the last report to the
        flight recorder (one small device read; only runs when a recorder
        is attached AND supervision is compiled in)."""
        if not self._core.sup.active:
            return
        totals = np.asarray(jax.device_get(self.sup_counts), np.int64)
        delta = totals - self._sup_reported
        if not delta.any():
            return
        self._sup_reported = totals
        fr.device_supervision("batched",
                              int(jax.device_get(self.step_count)),
                              *(int(x) for x in delta))

    def set_behavior(self, ids, behavior: BatchedBehavior | int) -> None:
        """Host-side become: rewrite the rows' behavior index."""
        b_idx = behavior if isinstance(behavior, int) \
            else self.behaviors.index(behavior)
        idx = jnp.asarray(np.atleast_1d(np.asarray(ids, np.int32)))
        self.behavior_id = self.behavior_id.at[idx].set(b_idx)

    @property
    def free_row_count(self) -> int:
        with self._lock:
            return len(self._free_rows) + (self.capacity - self._next_row)

    # ------------------------------------------------------------------ read
    def read_state(self, col: str, ids: Optional[np.ndarray] = None) -> np.ndarray:
        """Host copy of one state column. Implicitly drains the dispatch
        pipeline first (see failed_rows): a read during a full
        run_pipelined window must not observe donated buffers."""
        with self._host.read("state"):
            self.block_until_ready()
            arr = self.state[col]
            if ids is not None:
                arr = arr[jnp.asarray(ids)]
            return np.asarray(jax.device_get(arr))

    @property
    def dropped_messages(self) -> int:
        """Total host tells dropped on overflow. Derived from the stager's
        atomic counter (no racy Python increments — ADVICE r1) plus the
        lock-guarded Python-path count."""
        n = self._dropped_host
        if self._stager is not None:
            n += self._stager.dropped
        return n

    @property
    def mailbox_overflow(self) -> int:
        """Messages LOST on device (slots mode only). With the default
        spill region, slot overflow is retained and redelivered — this
        counts only spill-region overflow (a sustained burst larger than
        spill_capacity). With spill_capacity=0 (bounded mailboxes), every
        message past the S slots counts (dispatch/Mailbox.scala:415-443)."""
        return int(jax.device_get(self.mail_dropped))

    @property
    def live_count(self) -> int:
        return int(jnp.sum(self.alive.astype(jnp.int32)))

    @property
    def pending_messages(self) -> int:
        return int(jnp.sum(self.inbox_valid.astype(jnp.int32)))
