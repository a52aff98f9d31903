"""ShardedBatchedSystem: the actor space sharded over a device mesh.

This is the TPU-native analogue of cluster sharding's data plane
(sharding/ShardRegion.scala:1046 deliverMessage — resolve shard, forward) plus
Artery's transport (SURVEY.md §2.3): entities→shards→regions becomes
actors→shard-axis→devices, and a cross-shard tell becomes a slot in the
all_to_all exchange buffer inside the jitted step — messages ride ICI, never
the host.

Routing inside shard_map, per step:
1. deliver the local inbox (StepCore: segment reduction, or stable-sorted
   per-message mailbox slots — shared with BatchedSystem),
2. run the vmapped behavior switch (global actor ids),
3. bucket emitted messages by destination shard into a [D, C] exchange
   buffer (cpu/xla backends: rank-in-group over the narrow shard key, then
   scatter; reference, which is what a TPU runs: one full-column stable
   sort, then each shard's contiguous run copied out — no scatter;
   overflow drops are counted),
4. `lax.all_to_all` the buffer — each shard receives its [D, C] slice, which
   becomes the next step's inbox (self-addressed chunks deliver locally).

Bucketing is arrival-stable (a message's rank counts earlier emissions to
the same shard) and each shard's send buffer is drained in slot order, so
per-sender FIFO survives the exchange (messages from shard s to actor a
arrive in emission order); both strategies fill bit-identical buffers. Per-pair capacity C defaults to lossless
(all local emissions could target one shard). Static shapes throughout; the
whole step is one jitted program.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.segment import exchange_uses_ranked, stable_ranks
from ..parallel.mesh import make_mesh
from .behavior import BatchedBehavior
from .host_side import HostSide, build_span
from .metrics_slab import (ASK_ARM_COL, ASK_ARM_SPEC, N_BUCKETS, N_HIST,
                           accumulate_step, slab_dict)
from .step import StepCore
from .supervision import (ATT_WORDS, N_COUNTERS, SUP_COLUMNS, counts_dict,
                          decode_attention, reserved_fill)

# The step's carry, in the order `multi_step` takes and returns it: the
# system's attributes of these names. `mesh_stats`, where a system keeps it,
# rides behind `tables` and the step count (a reduce-mode system with a
# lossless exchange keeps none, and its program is the one it always was).
CARRY = ("state", "behavior_id", "alive", "inbox_dst", "inbox_type",
         "inbox_payload", "inbox_valid", "inbox_enq", "dropped",
         "mail_dropped", "sup_counts", "metrics", "step_count")
_AT = {name: i for i, name in enumerate(CARRY)}
_DONATED = tuple(range(_AT["step_count"]))  # every slot but the step count
# the lanes of `mesh_stats` ([n_shards, 3] int32, a row a shard)
SPILLED, SPILL_HIGH, EXCHANGE_HIGH = range(3)


def _bucket_by_sort(dest_shard, cols, fills, n_shards: int, pair_cap: int):
    """The sorted side of the exchange bucketing: the [n_shards * pair_cap]
    send buffers of the 1-D columns `cols`, chunk d holding the rows whose
    `dest_shard` is d in their original order, `fills[i]` after them.

    ONE stable sort on (dest_shard, row) carries every column; the rows for
    shard d are then the contiguous run [start_d, start_d + count_d), so
    chunk d is a copy of the run's first min(count_d, pair_cap) rows:
    `n_shards` contiguous copies per column (`dynamic_slice` at the run's
    start, masked to its length) — no rank, no slot index, no scatter.
    Rows whose `dest_shard` is `n_shards` (nowhere to go) sort last and are
    in no run. The columns stay 1-D throughout (a stacked [rows, p] array
    would leave on-chip memory on the TPU).

    Returns (one buffer per column, the `ok` mask of the rows that hold a
    message, the number of rows past `pair_cap` in their run: the
    exchange's drop count, and every run's length: what each chunk was
    asked to carry)."""
    m = dest_shard.shape[0]
    srt = jax.lax.sort(
        (dest_shard, jnp.arange(m, dtype=jnp.int32)) + tuple(cols),
        num_keys=2)
    ds_sorted = srt[0]
    # dynamic_slice clamps its start so that the slice fits: a run may start
    # at the last row, so give every column pair_cap rows of room
    padded = [jnp.concatenate([c, jnp.full((pair_cap,), f, c.dtype)])
              for c, f in zip(srt[2:], fills)]
    lane = jnp.arange(pair_cap, dtype=jnp.int32)
    chunks = [[] for _ in padded]
    oks, counts = [], []
    start = n_dropped = 0
    for d in range(n_shards):
        count = jnp.sum((ds_sorted == d).astype(jnp.int32))
        keep = lane < count
        for out, c, f in zip(chunks, padded, fills):
            run = jax.lax.dynamic_slice(c, (start,), (pair_cap,))
            out.append(jnp.where(keep, run, f))
        oks.append(keep)
        counts.append(count)
        n_dropped = n_dropped + jnp.maximum(count - pair_cap, 0)
        start = start + count
    return ([jnp.concatenate(ch) for ch in chunks], jnp.concatenate(oks),
            n_dropped, counts)


class ShardedBatchedSystem:
    @build_span
    def __init__(self, capacity: int, behaviors: Sequence[BatchedBehavior],
                 mesh: Optional[Mesh] = None, n_devices: Optional[int] = None,
                 payload_width: int = 4, out_degree: int = 1,
                 host_inbox_per_shard: int = 256,
                 remote_capacity_per_pair: Optional[int] = None,
                 payload_dtype=jnp.float32, axis_name: str = "shards",
                 mailbox_slots: int = 0, reroute_strays: bool = False,
                 spill_capacity: Optional[int] = None,
                 delivery: str = "auto",
                 delivery_backend: Optional[str] = None,
                 attention_latch_col: Optional[str] = None,
                 metrics_enabled: bool = False,
                 routers: Sequence[Any] = ()):
        if routers:
            # a message's rank among the step's tells to a router is a
            # prefix count over every chip's inbox, and a pool whose routees
            # span chips is a cluster-aware router: a deployment of its own
            # (docs/ROUTING.md)
            raise NotImplementedError(
                "ShardedBatchedSystem runs no pool router: the route stage "
                "(ISSUE 32, StepCore.route) ranks messages by a prefix "
                "count over ONE chip's inbox; across chips it needs a "
                "cross-chip prefix. Use BatchedSystem")
        # the host side's one bracket: spans, compile rows, host_stats()
        self._host = HostSide("sharded")
        self.mesh = mesh if mesh is not None else make_mesh(n_devices, axis_name)
        self.axis = axis_name
        self.n_shards = self.mesh.shape[axis_name]
        if capacity % self.n_shards != 0:
            capacity += self.n_shards - capacity % self.n_shards
        self.capacity = capacity
        self.local_n = capacity // self.n_shards
        self.behaviors = list(behaviors)
        self.payload_width = payload_width
        self.out_degree = out_degree
        self.host_inbox = host_inbox_per_shard
        self.payload_dtype = payload_dtype
        self.mailbox_slots = int(mailbox_slots)
        if self.mailbox_slots == 0 and any(b.inbox == "slots" for b in behaviors):
            self.mailbox_slots = max(2, out_degree)
        # per-shard spill region: unbounded-mailbox semantics in slots mode
        # (overflow + suspended-row mail retained, redelivered next step
        # ahead of fresh traffic — see BatchedSystem)
        if self.mailbox_slots > 0:
            self.spill_cap = (int(spill_capacity) if spill_capacity is not None
                              else max(self.host_inbox,
                                       4 * self.mailbox_slots))
        else:
            self.spill_cap = 0
        # forward inbox messages whose home shard moved (rebalance) one
        # more hop instead of dropping them. The stray pass costs a 2x
        # exchange sort + 2x delivery input, so it is a MODE, not an
        # always-on tax (r4 weak #5: the always-on pass made the public
        # sharding API 3-5x slower than the raw runtime in steady state):
        # enter_stray_mode() at rebalance, exit_stray_mode() once drained.
        # The reference shape is the same — ShardRegion buffers/forwards
        # only DURING hand-off (ShardRegion.scala:968,1056), while
        # deliverMessage stays a hash + table lookup (:1046).
        self.reroute_strays = bool(reroute_strays)
        self.stray_mode = False
        # narrow seam for the local-delivery kernel family (segment.py):
        # None/"auto" = per-platform cost model, "xla" = rank-then-scatter,
        # "reference" = frozen wide-sort kernels. Results are bit-identical
        # either way; the knob only moves work off the sort network.
        self.delivery_backend = delivery_backend
        # lossless default: every local emission could target a single
        # shard; in stray mode, one rebalanced block's worth of forwarded
        # in-flight messages can ride alongside a full emission batch, so
        # stray sizing doubles (overflow is still counted either way —
        # `dropped` is the guard, this is the sizing heuristic)
        if remote_capacity_per_pair:
            # an EXPLICIT cap is a memory bound the user provisioned for:
            # honor it in both modes (overflow is counted in `dropped`,
            # exactly as before the mode split)
            self.pair_cap_base = remote_capacity_per_pair
            self.pair_cap_stray = remote_capacity_per_pair
        else:
            self.pair_cap_base = self.local_n * out_degree
            self.pair_cap_stray = 2 * self.pair_cap_base
        self.pair_cap = self.pair_cap_base

        self.state_spec: Dict[str, Tuple[Tuple[int, ...], Any]] = {}
        for b in self.behaviors:
            for col, spec in b.state_spec.items():
                if col in self.state_spec and self.state_spec[col] != spec:
                    raise ValueError(f"conflicting column {col!r}")
                self.state_spec[col] = (tuple(spec[0]), spec[1])
        # in-graph supervision columns (batched/supervision.py): sharded
        # with the state, so supervision bookkeeping survives the exchange
        # and a rebalance relocating a failed lane moves its retry/backoff
        # state with it
        if any(getattr(b, "supervisor", None) is not None for b in behaviors):
            for col, spec in SUP_COLUMNS.items():
                self.state_spec.setdefault(col, spec)
        elif any(getattr(b, "nonfinite_guard", False) for b in behaviors):
            self.state_spec.setdefault("_failed", SUP_COLUMNS["_failed"])
        # telemetry plane (metrics_slab.py): per-shard histogram slab rides
        # the carry like sup_counts; ask-latency needs the arm-stamp column
        # sharded with the state so a rebalanced promise row keeps its clock
        self.metrics_on = bool(metrics_enabled)
        if self.metrics_on and attention_latch_col is not None:
            self.state_spec.setdefault(ASK_ARM_COL, ASK_ARM_SPEC)

        shard = NamedSharding(self.mesh, P(axis_name))
        n = self.capacity
        self.state = {k: jax.device_put(jnp.zeros((n,) + shape, dtype=dtype), shard)
                      for k, (shape, dtype) in self.state_spec.items()}
        for col in self.state:  # _become/_restart_at re-arm to -1, not 0
            if reserved_fill(col):
                self.state[col] = jax.device_put(
                    jnp.full((n,), reserved_fill(col),
                             self.state_spec[col][1]), shard)
        self.behavior_id = jax.device_put(jnp.zeros((n,), jnp.int32), shard)
        self.alive = jax.device_put(jnp.zeros((n,), jnp.bool_), shard)
        # committed + replicated on the mesh from the start: an uncommitted
        # scalar would change sharding after the first step and force a
        # SECOND full compile (observed: 2x ~2s at tiny sizes on CPU)
        self.step_count = jax.device_put(
            jnp.asarray(0, jnp.int32), NamedSharding(self.mesh, P()))

        # inbox per shard: spill slots first (older mail outranks fresh in
        # the stable delivery sort), then D*C exchange slots, then host slots
        self.m_local = self.spill_cap + self.n_shards * self.pair_cap \
            + self.host_inbox
        m_global = self.m_local * self.n_shards
        self.inbox_dst = jax.device_put(jnp.full((m_global,), -1, jnp.int32), shard)
        self.inbox_type = jax.device_put(jnp.zeros((m_global,), jnp.int32), shard)
        self.inbox_payload = jax.device_put(
            jnp.zeros((m_global, payload_width), payload_dtype), shard)
        self.inbox_valid = jax.device_put(jnp.zeros((m_global,), jnp.bool_), shard)
        # enqueue-step stamps for the sojourn lane (metrics_slab.py): one
        # int32 per inbox row when metrics are on, a zero-size placeholder
        # otherwise so the carry structure is static either way
        self.inbox_enq = jax.device_put(
            jnp.zeros((m_global,) if self.metrics_on else (0,), jnp.int32),
            shard)
        self.dropped = jax.device_put(jnp.zeros((self.n_shards,), jnp.int32), shard)
        self.mail_dropped = jax.device_put(
            jnp.zeros((self.n_shards,), jnp.int32), shard)
        # [n_shards, 3], a row a shard: `spilled`, the messages the shard's
        # spill region carried over to a next step, summed over the steps
        # run (int32, modulo 2^32); `spill_high_water`, the most it held
        # after any one step; `exchange_high_water`, the most messages any
        # one of the shard's pair chunks was asked to carry in a step
        # (beyond `pair_cap` the rest was dropped, and `dropped` counts
        # it). Kept where there is something to judge, a spill region or a
        # provisioned `remote_capacity_per_pair`; None elsewhere, and the
        # step program then carries nothing of it
        self.mesh_stats = jax.device_put(
            jnp.zeros((self.n_shards, 3), jnp.int32), shard) \
            if self.spill_cap > 0 or remote_capacity_per_pair else None
        # what the host last read of them (host_stats() makes no sync)
        self._stats_read = {}
        # per-shard in-graph supervision counters ([n_shards, N_COUNTERS],
        # COUNTER_NAMES order) — summed over shards on host read
        self.sup_counts = jax.device_put(
            jnp.zeros((self.n_shards, N_COUNTERS), jnp.int32), shard)
        # per-shard metric slab ([n_shards, N_HIST, N_BUCKETS]) — summed
        # over shards on host drain, exactly like sup_counts. Allocated
        # even when off: static carry structure, trace-time gating.
        self.metrics = jax.device_put(
            jnp.zeros((self.n_shards, N_HIST, N_BUCKETS), jnp.int32), shard)
        # epoch word (slab running sum): a non-donated replicated output of
        # every run(), read with one scalar fetch to decide if a full slab
        # drain is worth the bytes (drain_metrics)
        self.metrics_epoch = jax.device_put(
            jnp.asarray(0, jnp.int32), NamedSharding(self.mesh, P()))
        self._metrics_seen_epoch = 0
        # host-attention words (supervision.pack_attention): one
        # [ATT_WORDS] row PER SHARD, sharded with everything else, each
        # recomputed from the final carry of every run(). The pipelined
        # driver syncs on this handle instead of step_count and reads the
        # whole mesh's flags/counters/progress lanes with ONE tiny
        # device_get — row s's ATT_PROGRESS is shard s's heartbeat (the
        # MeshSentinel's detection input, batched/sentinel.py)
        self.attention = jax.device_put(
            jnp.zeros((self.n_shards, ATT_WORDS), jnp.int32), shard)
        # cumulative per-shard overflow already reported via the
        # shard_overflow flight-recorder warning (read_attention)
        self._overflow_reported = np.zeros((self.n_shards, 2), np.int64)
        # optional FlightRecorder (event/flight_recorder.py SPI); the
        # sentinel wires its recorder here so shard_overflow warnings and
        # checkpoint events share one stream. None = zero overhead.
        self.flight_recorder = None

        self._next_row = 0
        self._lock = threading.Lock()
        self._host_staged: List[Tuple[int, int, np.ndarray]] = []
        # host mirror of the dispatched-step counter + optional write-ahead
        # tell journal (persistence/tell_journal.py) — see BatchedSystem
        self._host_step = 0
        self.tell_journal = None
        # small replicated lookup tables exposed to behaviors via
        # ctx.tables (e.g. device-sharding placement). Set BEFORE first
        # run; keys are fixed per built step function.
        self.tables: Dict[str, jax.Array] = {}

        self._core = StepCore(self.behaviors, n_local=self.local_n,
                              payload_width=payload_width,
                              out_degree=out_degree,
                              payload_dtype=payload_dtype,
                              slots=self.mailbox_slots,
                              n_global=self.capacity,
                              delivery=delivery,
                              delivery_backend=delivery_backend,
                              spill_cap=self.spill_cap,
                              attention_latch_col=attention_latch_col)
        self._step_fn = None  # built lazily: tables may be set post-init
        self._step_cache: Dict[bool, Any] = {}  # stray-mode -> compiled step

    # -------------------------------------------------------------- builders
    def _build_step(self, stray: bool = False):
        n_local, n_shards, k_out = self.local_n, self.n_shards, self.out_degree
        p_w, dtype = self.payload_width, self.payload_dtype
        pair_cap, m_local, axis = self.pair_cap, self.m_local, self.axis
        n_global = self.capacity
        core = self._core
        platform = self.mesh.devices.flat[0].platform
        ranked_exchange = exchange_uses_ranked(platform, self.delivery_backend)

        def local_step(state, behavior_id, alive, inbox_dst, inbox_type,
                       inbox_payload, inbox_valid, inbox_enq, dropped,
                       mail_dropped, sup_counts, metrics, step_count, tables,
                       *stats):
            # shapes here are per-shard blocks; `stats` is the shard's row
            # of `mesh_stats`, or nothing
            shard_idx = jax.lax.axis_index(axis)
            base = shard_idx * n_local
            old_state, old_alive = state, alive

            (new_state, behavior_id, alive, emits, mdrop, spill,
             sup_delta, dcount) = core.run_local(
                state, behavior_id, alive, inbox_dst, inbox_type,
                inbox_payload, inbox_valid, step_count,
                dst_offset=base, id_base=base, tables=tables)

            # ---- route: bucket by destination shard, exchange over ICI ----
            # Two bucketing strategies behind the delivery_backend seam,
            # producing bit-identical exchange buffers: row r <
            # min(count_d, pair_cap) of chunk d is the r-th row for shard d
            # in stable order (strays first in stray mode), everything
            # past it fill.
            #  * ranked (cpu/xla): stable_ranks over the narrow shard key
            #    only — dst/type/payload scatter straight from the original
            #    domain and never ride a sort network;
            #  * reference: ONE stable keyed sort carries every column
            #    through the sort network (argsort + x[order] gathers
            #    serialize on TPU); shard d's rows are then one contiguous
            #    run, and its chunk is a copy of the run's head
            #    (_bucket_by_sort: nothing data-addressed).
            slots_mode = self.mailbox_slots > 0
            with jax.named_scope("akka.exchange"), \
                    jax.named_scope("akka.exchange.bucket"):
                out_dst = emits.dst.reshape(-1)                   # [n_local*k]
                out_payload = emits.payload.reshape(-1, p_w)
                out_type = emits.type.reshape(-1)
                out_valid = emits.valid.reshape(-1) & (out_dst >= 0) \
                    & (out_dst < n_global)
                if stray:
                    # inbox rows addressed OUTSIDE this shard (a shard was
                    # rebalanced after the message was exchanged): forward
                    # them one more hop instead of dropping — ShardRegion
                    # buffering-during-handoff semantics
                    # (ShardRegion.scala:968,1056). Strays ride FIRST (they
                    # are older; the sort is stable).
                    stray_ok = inbox_valid & (inbox_dst >= 0) & \
                        ((inbox_dst < base) | (inbox_dst >= base + n_local))
                    out_dst = jnp.concatenate([
                        jnp.where(stray_ok, inbox_dst, -1), out_dst])
                    out_payload = jnp.concatenate([inbox_payload,
                                                   out_payload])
                    out_type = jnp.concatenate([inbox_type, out_type])
                    out_valid = jnp.concatenate([stray_ok, out_valid])
                # a row whose dest_shard < n_shards is valid by construction
                dest_shard = jnp.where(out_valid, out_dst // n_local,
                                       n_shards)

                m = out_dst.shape[0]
                ds32 = dest_shard.astype(jnp.int32)
                if ranked_exchange:
                    # the shard-id domain is tiny (n_shards + 2 <= 64 for
                    # every deployed mesh), so on CPU stable_ranks
                    # auto-resolves to ONE counting pass — the exchange
                    # buckets with no sort network at all (accelerators keep
                    # the 2-operand sort)
                    rank, counts = stable_ranks(ds32, n_shards, platform)
                    counts = counts[:n_shards]
                    in_cap = out_valid & (rank < pair_cap) & (ds32 < n_shards)
                    slot = jnp.where(in_cap, ds32 * pair_cap + rank,
                                     n_shards * pair_cap)  # overflow bucket
                    n_dropped = jnp.sum(
                        (out_valid & ~in_cap).astype(jnp.int32))
                    rows = n_shards * pair_cap + 1

                    def scattered(col, fill):
                        keep = in_cap.reshape((m,) + (1,) * (col.ndim - 1))
                        buf = jnp.full((rows,) + col.shape[1:], fill,
                                       col.dtype)
                        return buf.at[slot].set(
                            jnp.where(keep, col, fill))[:-1]

                    buf_dst = scattered(out_dst, -1)
                    buf_pl = scattered(out_payload, 0)
                    buf_ok = scattered(in_cap, False)
                    if slots_mode:
                        buf_type = scattered(out_type, 0)
                else:
                    # the type column rides only if somebody reads it —
                    # reduce-mode systems skip a whole collective
                    tcol = (out_type,) if slots_mode else ()
                    fcols = tuple(out_payload[:, i] for i in range(p_w))
                    bufs, buf_ok, n_dropped, counts = _bucket_by_sort(
                        ds32, (out_dst,) + tcol + fcols,
                        (-1,) + (0,) * (len(tcol) + p_w), n_shards, pair_cap)
                    buf_dst = bufs[0]
                    if slots_mode:
                        buf_type = bufs[1]
                    buf_pl = jnp.stack(bufs[1 + len(tcol):], axis=1)
                if stats:
                    fullest = jnp.max(jnp.asarray(counts, jnp.int32))

            # all_to_all: chunk d of my buffer -> shard d; I receive
            # chunk-for-me from every shard (self chunk included -> local
            # messages loop back)
            with jax.named_scope("akka.exchange"), \
                    jax.named_scope("akka.exchange.all_to_all"):
                def exchange(buf, *tail):
                    return jax.lax.all_to_all(
                        buf.reshape(n_shards, pair_cap, *tail), axis, 0, 0,
                        tiled=False).reshape(-1, *tail)

                recv_dst = exchange(buf_dst)
                recv_pl = exchange(buf_pl, p_w)
                recv_ok = exchange(buf_ok)
                if slots_mode:
                    recv_type = exchange(buf_type)

            # write received chunks in place over the donated inbox block
            # at the exchange offset (after the spill region); host rows
            # (the tail) are cleared
            sc = self.spill_cap
            r = recv_dst.shape[0]
            upd = jax.lax.dynamic_update_slice
            with jax.named_scope("akka.exchange"), \
                    jax.named_scope("akka.exchange.unpack"):
                new_inbox_dst = upd(inbox_dst, recv_dst,
                                    (sc,)).at[sc + r:].set(-1)
                if slots_mode:
                    new_inbox_type = upd(inbox_type, recv_type,
                                         (sc,)).at[sc + r:].set(0)
                else:
                    new_inbox_type = inbox_type  # never read in reduce mode
                new_inbox_payload = upd(inbox_payload, recv_pl,
                                        (sc, 0)).at[sc + r:].set(0)
                new_inbox_valid = upd(inbox_valid, recv_ok,
                                      (sc,)).at[sc + r:].set(False)
            carried = 0
            if spill is not None:  # spill is None iff sc == 0
                # retained spill lands FIRST
                with jax.named_scope("akka.emit"), \
                        jax.named_scope("akka.emit.spill"):
                    sp_dst, sp_type, sp_pl, sp_v = spill
                    new_inbox_dst = new_inbox_dst.at[:sc].set(sp_dst)
                    new_inbox_type = new_inbox_type.at[:sc].set(sp_type)
                    new_inbox_payload = new_inbox_payload.at[:sc].set(sp_pl)
                    new_inbox_valid = new_inbox_valid.at[:sc].set(sp_v)
                    carried = jnp.sum(sp_v.astype(jnp.int32))
            if stats:
                old = stats[0][0]
                stats = (jnp.stack([
                    old[SPILLED] + carried,
                    jnp.maximum(old[SPILL_HIGH], carried),
                    jnp.maximum(old[EXCHANGE_HIGH], fullest)])[None],)
            new_dropped = dropped + n_dropped
            new_mail_dropped = mail_dropped + mdrop
            new_sup_counts = sup_counts + sup_delta[None, :]

            if self.metrics_on:
                # histograms read THIS step's inputs (old state, the inbox
                # we just delivered from, its enqueue stamps); the per-shard
                # slab block is [1, N_HIST, N_BUCKETS], same row trick as
                # sup_counts
                with jax.named_scope("akka.metrics"):
                    new_metrics = accumulate_step(
                        metrics[0], old_state, new_state, old_alive, dcount,
                        inbox_valid, inbox_enq, step_count,
                        latch_col=core.attention_latch_col)[None]
                    # received rows are RE-stamped with the local clock
                    # instead of exchanging the writer's stamp (no extra
                    # collective; a stray forward resets the age clock —
                    # docs/OBSERVABILITY.md)
                    stamp = jnp.broadcast_to(
                        jnp.asarray(step_count, jnp.int32), (r,))
                    new_inbox_enq = upd(inbox_enq, stamp,
                                        (sc,)).at[sc + r:].set(0)
                    if spill is not None:
                        # spill rows are a compacted permutation of the old
                        # inbox, so stamps can't be copied positionally:
                        # re-arm at injection (age counts steps since last
                        # (re)stamp, same rule as the single-device runtime)
                        new_inbox_enq = new_inbox_enq.at[:sc].set(
                            jnp.asarray(step_count, jnp.int32))
            else:
                new_metrics = metrics
                new_inbox_enq = inbox_enq

            return (new_state, behavior_id, alive, new_inbox_dst,
                    new_inbox_type, new_inbox_payload, new_inbox_valid,
                    new_inbox_enq, new_dropped, new_mail_dropped,
                    new_sup_counts, new_metrics, step_count + 1) + stats

        mesh = self.mesh
        state_specs = {k: P(axis) for k in self.state_spec}
        table_specs = {k: P() for k in self.tables}  # replicated, tiny
        kept = self.mesh_stats is not None
        n_carry = len(CARRY)
        in_specs = (state_specs, P(axis), P(axis), P(axis), P(axis), P(axis),
                    P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
                    P(), table_specs) + (P(axis),) * kept
        out_specs = (state_specs, P(axis), P(axis), P(axis), P(axis), P(axis),
                     P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
                     P()) + (P(axis),) * kept

        sharded = shard_map(local_step, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)

        # per-shard attention packing over the final carry: each shard
        # reduces ITS local blocks into one [ATT_WORDS] row (local flags,
        # local overflow counters, its own progress lane), so the stacked
        # [n_shards, ATT_WORDS] word stays sharded and a single host fetch
        # reads every shard's heartbeat
        att_map = shard_map(
            lambda st, dr, md, sc_, stp: core.attention_word(
                st, md, sc_, stp, exch_dropped=dr).reshape(1, ATT_WORDS),
            mesh=mesh,
            in_specs=(state_specs, P(axis), P(axis), P(axis), P()),
            out_specs=P(axis), check_vma=False)

        def multi_step(state, behavior_id, alive, inbox_dst, inbox_type,
                       inbox_payload, inbox_valid, inbox_enq, dropped,
                       mail_dropped, sup_counts, metrics, step_count, tables,
                       n_steps: int, *mesh_stats):
            def body(carry, _):
                return sharded(*carry[:n_carry], tables,
                               *carry[n_carry:]), None
            carry = (state, behavior_id, alive, inbox_dst, inbox_type,
                     inbox_payload, inbox_valid, inbox_enq, dropped,
                     mail_dropped, sup_counts, metrics,
                     step_count) + mesh_stats
            carry, _ = jax.lax.scan(body, carry, None, length=n_steps)
            # host-attention words from the final carry: every field is
            # carry-derived (flags = current state, counters cumulative),
            # so one per-shard reduction per run() covers the window —
            # nothing rides the scan. Appended OUTSIDE the donation set.
            attention = att_map(*(carry[_AT[k]] for k in (
                "state", "dropped", "mail_dropped", "sup_counts",
                "step_count")))
            # metrics epoch: the slab's running sum, same non-donated trick
            epoch = (jnp.sum(carry[_AT["metrics"]]).astype(jnp.int32)
                     if self.metrics_on else jnp.asarray(0, jnp.int32))
            return carry[:n_carry] + (attention, epoch) + carry[n_carry:]

        # pin output shardings to the INPUT shardings: without this, GSPMD
        # may normalize an output (observed: inbox_payload -> replicated on
        # a 1-device mesh), the carry's sharding then differs from the
        # first compile's inputs, and every run after the first recompiles
        shard_s = NamedSharding(mesh, P(axis))
        repl_s = NamedSharding(mesh, P())
        out_shardings = ({k: shard_s for k in self.state_spec},
                         shard_s, shard_s, shard_s, shard_s, shard_s,
                         shard_s, shard_s, shard_s, shard_s, shard_s,
                         shard_s, repl_s, shard_s, repl_s) + (shard_s,) * kept
        # arguments: the carry, the tables, the step count (static), then
        # `mesh_stats` where the system keeps it
        return jax.jit(multi_step, static_argnums=(n_carry + 1,),
                       donate_argnums=_DONATED + (n_carry + 2,) * kept,
                       out_shardings=out_shardings)

    # ------------------------------------------------------------- lifecycle
    def spawn_block(self, behavior: BatchedBehavior | int, n: int,
                    init_state: Optional[Dict[str, Any]] = None) -> np.ndarray:
        b_idx = behavior if isinstance(behavior, int) else self.behaviors.index(behavior)
        with self._lock:
            start = self._next_row
            if start + n > self.capacity:
                raise RuntimeError("actor capacity exhausted")
            self._next_row = start + n
        # pow2-with-floor-64 padded index scatter (the _flush_staged rule):
        # a duplicated leading index re-set to the identical value is
        # idempotent, and the padded shape bounds the compiled-scatter
        # count. Unpadded slice-sets compile one program per distinct
        # block length AND per mesh — on a failover/scale re-shard every
        # replayed spawn block would pay a fresh ~1s eager XLA compile on
        # CPU, dominating the measured re-shard pause.
        pad = max(64, 1 << (n - 1).bit_length()) - n
        rows_np = np.arange(start, start + n, dtype=np.int32)
        idx = jnp.asarray(np.concatenate(
            [rows_np, np.full(pad, start, np.int32)]) if pad else rows_np)
        self.behavior_id = self.behavior_id.at[idx].set(b_idx)
        self.alive = self.alive.at[idx].set(True)
        if init_state:
            for col, value in init_state.items():
                cur = self.state[col]
                v = jnp.asarray(value, dtype=cur.dtype)
                if v.ndim == cur.ndim and v.shape[0] == n:
                    # per-row values: pad rows exactly like the indices
                    if pad:
                        v = jnp.concatenate(
                            [v, jnp.broadcast_to(v[:1],
                                                 (pad,) + v.shape[1:])])
                    self.state[col] = cur.at[idx].set(v)
                else:
                    self.state[col] = cur.at[idx].set(v)
        return rows_np

    def spawn_layout(self, behavior_ids, init_state: Dict[str, Any]) -> None:
        """Spawn EVERY row at once from host arrays, for a deployment whose
        behaviors interleave over the mesh (a chip's entities, then its
        clients): row r runs `behaviors[behavior_ids[r]]`, and
        `init_state[col]` is the whole column. One placement a column,
        under the mesh's sharding; `spawn_block` writes a contiguous block
        with a scatter a column. The system must be empty."""
        ids = np.asarray(behavior_ids, np.int32)
        with self._lock:
            if self._next_row or ids.shape != (self.capacity,):
                raise RuntimeError("spawn_layout fills an empty system, "
                                   "every row of it")
            self._next_row = self.capacity
        shard = NamedSharding(self.mesh, P(self.axis))
        self.behavior_id = jax.device_put(ids, shard)
        self.alive = jax.device_put(np.ones(self.capacity, np.bool_), shard)
        for col, value in init_state.items():
            dtype = self.state_spec[col][1]
            self.state[col] = jax.device_put(np.asarray(value, dtype), shard)

    def tell(self, dst: int, payload, mtype: int = 0) -> None:
        pl = np.zeros(self.payload_width, dtype=jnp.dtype(self.payload_dtype))
        arr = np.asarray(payload).reshape(-1)
        pl[: arr.shape[0]] = arr
        if self.tell_journal is not None:
            # WAL: journal the normalized row BEFORE staging (see
            # BatchedSystem.tell)
            self.tell_journal.append(self._host_step, "tell",
                                     int(dst), pl, int(mtype))
        with self._lock:
            self._host_staged.append((int(dst), int(mtype), pl))

    def _flush_staged(self) -> None:
        with self._lock:
            staged, self._host_staged = self._host_staged, []
        if not staged:
            return
        # host slots live at the tail of each shard's inbox block; place each
        # message in its destination shard's host region
        per_shard_used: Dict[int, int] = {}
        idxs, dsts, mts, pls = [], [], [], []
        for d, t, p in staged:
            s = d // self.local_n
            u = per_shard_used.get(s, 0)
            if u >= self.host_inbox:
                continue
            per_shard_used[s] = u + 1
            idxs.append(s * self.m_local + self.spill_cap
                        + self.n_shards * self.pair_cap + u)
            dsts.append(d)
            mts.append(t)
            pls.append(p)
        if not idxs:
            return
        # pad to the next power of two (floor 64) by repeating the first
        # record: a duplicate scatter index carrying identical values is
        # idempotent, and the padded shape bounds the compiled-scatter
        # count — the floor means every flush up to 64 records shares ONE
        # compiled program. Unpadded, .at[idx].set compiles a fresh
        # program for EVERY distinct flush count — invisible when tells
        # trickle in one per step, ruinous once the batched ask engine
        # flushes whole batches whose sizes vary with concurrency.
        n = len(idxs)
        pad = max(64, 1 << (n - 1).bit_length()) - n
        if pad:
            idxs.extend(idxs[:1] * pad)
            dsts.extend(dsts[:1] * pad)
            mts.extend(mts[:1] * pad)
            pls.extend(pls[:1] * pad)
        idx = jnp.asarray(idxs)
        self.inbox_dst = self.inbox_dst.at[idx].set(jnp.asarray(dsts, jnp.int32))
        self.inbox_type = self.inbox_type.at[idx].set(jnp.asarray(mts, jnp.int32))
        self.inbox_payload = self.inbox_payload.at[idx].set(
            jnp.asarray(np.stack(pls), self.payload_dtype))
        self.inbox_valid = self.inbox_valid.at[idx].set(True)
        if self.metrics_on:
            # host flush stamps with the dispatched-step mirror: the rows
            # are delivered by the next dispatched step, so a drained
            # pipeline reads sojourn age 0 for host mail (fused-flush
            # convention, BatchedSystem._flush_impl)
            self.inbox_enq = self.inbox_enq.at[idx].set(self._host_step)

    def set_tables(self, tables: Dict[str, Any]) -> None:
        """Install/replace the replicated lookup tables behaviors see via
        ctx.tables. Changing the KEY SET after the first run retraces the
        step program; changing only the values does not."""
        rebuild = set(tables) != set(self.tables) and \
            (self._step_fn is not None or self._step_cache)
        self.tables = {k: jnp.asarray(v) for k, v in tables.items()}
        if rebuild:
            self._step_cache.clear()
            self._step_fn = None

    # ------------------------------------------------------- stray handoff
    def _relayout_inbox(self, new_pair_cap: int) -> None:
        """Re-grid the inbox buffers for a different per-pair exchange
        capacity. Layout per shard block: [spill | n_shards*pair_cap |
        host]; within each pair chunk, received rows are rank-packed at
        the chunk start, so growing pads each chunk's tail and shrinking
        slices it (the caller has verified the tail is empty)."""
        if new_pair_cap == self.pair_cap:
            return  # explicit remote_capacity_per_pair: both modes share
            #         the sizing, the regrid would be a full no-op copy
        ns, sc, hi = self.n_shards, self.spill_cap, self.host_inbox
        old_pc, old_ml = self.pair_cap, self.m_local
        new_ml = sc + ns * new_pair_cap + hi
        shard = NamedSharding(self.mesh, P(self.axis))

        def regrid(arr, fill):
            tail_shape = arr.shape[1:]
            v = arr.reshape(ns, old_ml, *tail_shape)
            spill = v[:, :sc]
            pairs = v[:, sc:sc + ns * old_pc].reshape(
                ns, ns, old_pc, *tail_shape)
            host = v[:, sc + ns * old_pc:]
            if new_pair_cap > old_pc:
                pad = jnp.full((ns, ns, new_pair_cap - old_pc, *tail_shape),
                               fill, arr.dtype)
                pairs = jnp.concatenate([pairs, pad], axis=2)
            else:
                pairs = pairs[:, :, :new_pair_cap]
            out = jnp.concatenate(
                [spill, pairs.reshape(ns, ns * new_pair_cap, *tail_shape),
                 host], axis=1)
            return jax.device_put(out.reshape(ns * new_ml, *tail_shape),
                                  shard)

        self.inbox_dst = regrid(self.inbox_dst, -1)
        self.inbox_type = regrid(self.inbox_type, 0)
        self.inbox_payload = regrid(self.inbox_payload, 0)
        self.inbox_valid = regrid(self.inbox_valid, False)
        if self.metrics_on:  # (0,) placeholder when off — nothing to regrid
            self.inbox_enq = regrid(self.inbox_enq, 0)
        self.pair_cap = new_pair_cap
        self.m_local = new_ml

    def enter_stray_mode(self) -> None:
        """Switch to the hand-off step variant: 2x per-pair exchange
        capacity and the stray-forwarding pass (inbox rows addressed
        outside their shard ride the next exchange). Call at rebalance;
        exit once drained — the variant costs ~2x per step."""
        if not self.reroute_strays:
            raise RuntimeError(
                "system built with reroute_strays=False has no stray step")
        if self.stray_mode:
            return
        self._relayout_inbox(self.pair_cap_stray)
        self.stray_mode = True

    def exit_stray_mode(self) -> bool:
        """Back to the steady-state step once it is SAFE: (a) no stray
        rows remain anywhere in the inbox (a stray surviving into the
        non-stray step would be silently erased by the next exchange), and
        (b) no pair chunk holds rows past the base capacity (the shrink
        slices chunk tails). Returns False — staying in stray mode — if
        forwarded traffic is still in flight on either count."""
        if not self.stray_mode:
            return True
        ns, sc = self.n_shards, self.spill_cap
        # both predicates reduce ON DEVICE; only two booleans cross to the
        # host (full-inbox device_gets per drain probe would put two
        # m_global-row transfers on the rebalance latency path)
        valid = self.inbox_valid.reshape(ns, self.m_local)
        dst = self.inbox_dst.reshape(ns, self.m_local)
        bases = (jnp.arange(ns, dtype=jnp.int32) * self.local_n)[:, None]
        # (a) any valid row addressed outside its hosting shard's range?
        has_stray = jnp.any(valid & ((dst < bases) |
                                     (dst >= bases + self.local_n)))
        # (b) any legit row parked past the base capacity of its chunk?
        pairs_valid = valid[:, sc:sc + ns * self.pair_cap].reshape(
            ns, ns, self.pair_cap)
        tail_occupied = jnp.any(pairs_valid[:, :, self.pair_cap_base:]) \
            if self.pair_cap_base < self.pair_cap else jnp.asarray(False)
        if bool(jax.device_get(has_stray)) or \
                bool(jax.device_get(tail_occupied)):
            return False
        self._relayout_inbox(self.pair_cap_base)
        self.stray_mode = False
        return True

    # ------------------------------------------------------------------ step
    def _carry(self):
        return tuple(getattr(self, name) for name in CARRY)

    def _kept_stats(self):
        return () if self.mesh_stats is None else (self.mesh_stats,)

    def _set_carry(self, out) -> None:
        # a run's output: the slots of CARRY, the non-donated attention
        # words and metrics epoch, then `mesh_stats` where it is kept
        for name, value in zip(CARRY, out):
            setattr(self, name, value)
        self.attention, self.metrics_epoch = out[len(CARRY):len(CARRY) + 2]
        if self.mesh_stats is not None:
            self.mesh_stats = out[len(CARRY) + 2]

    def run(self, n_steps: int = 1) -> None:
        self._step_fn = self._step_cache.get(self.stray_mode)
        if self._step_fn is None:
            self._step_fn = self._step_cache[self.stray_mode] = \
                self._build_step(self.stray_mode)
        self._flush_staged()
        with self._host.dispatch(f"akka.device.run[{n_steps}]", self,
                                 n_steps):
            self._set_carry(self._step_fn(*self._carry(), self.tables,
                                          n_steps, *self._kept_stats()))
        self._host_step += int(n_steps)

    step = run

    def run_pipelined(self, n_steps: int, depth: int = 2,
                      on_attention=None) -> None:
        """Single-step dispatches with up to `depth` in flight (see
        BatchedSystem.run_pipelined): hides host launch latency
        behind the mesh step; donated carries make the overlap free.
        Syncs on the host-attention word; with `on_attention`, every
        retired step's decoded word is delivered in order and the tail is
        fully drained (the narrow-readback drain the bridge pump uses)."""
        from .core import drive_pipelined
        cb = None
        if on_attention is not None:
            cb = lambda w: on_attention(decode_attention(w))  # noqa: E731
        drive_pipelined(lambda: self.run(1), lambda: self.attention,
                        n_steps, depth, on_drain=cb)

    def read_attention(self) -> Dict[str, Any]:
        """Decode the newest host-attention words — one tiny device_get
        that also syncs the newest dispatched run (non-donated output).
        The decoded dict carries per-shard columns (`*_per_shard`) on top
        of the global totals: `mail_dropped_per_shard` / `dropped_per_shard`
        localize overflow to the shard losing mail, and
        `progress_per_shard` is the heartbeat lane. A shard whose overflow
        counters GREW since the last read raises one `shard_overflow`
        flight-recorder warning — the "slow shard" signal, distinct from
        the frozen-progress "dead shard" signal the sentinel acts on."""
        word = decode_attention(self.attention)
        self._note_shard_overflow(word)
        return word

    def _note_shard_overflow(self, word: Dict[str, Any]) -> None:
        fr = self.flight_recorder
        if fr is None:
            return
        mail = np.asarray(word.get("mail_dropped_per_shard", ()), np.int64)
        exch = np.asarray(word.get("dropped_per_shard", ()), np.int64)
        if mail.shape[0] != self.n_shards:
            return  # decoded from a foreign/legacy word; nothing to localize
        # `mail_dropped` counts what a spill region could not hold where
        # the system has one, and what a bounded mailbox could not elsewhere
        mail_is = "spill" if self.spill_cap > 0 else "mailbox"
        for s in range(self.n_shards):
            seen_mail, seen_exch = self._overflow_reported[s]
            grew = (mail_is,) * bool(mail[s] > seen_mail) \
                + ("exchange",) * bool(exch[s] > seen_exch)
            if grew:
                fr.shard_overflow("sharded", shard=s,
                                  mailbox_overflow=int(mail[s]),
                                  dropped=int(exch[s]), overflowed=grew)
                self._overflow_reported[s] = (int(mail[s]), int(exch[s]))

    def read_state(self, col: str, ids: Optional[np.ndarray] = None) -> np.ndarray:
        """Host copy of one state column. Implicitly drains the dispatch
        pipeline first: with run_pipelined steps in flight the slabs are
        donated/aliased buffers that some platforms report ready early, so
        host reads sync on the non-donated step_count before touching
        them."""
        with self._host.read("state"):
            self.block_until_ready()
            arr = self.state[col]
            if ids is not None:
                arr = arr[jnp.asarray(ids)]
            return np.asarray(jax.device_get(arr))

    def any_failed(self) -> bool:
        from .step import fault_any_failed
        return fault_any_failed(self.state)

    def failed_rows(self) -> np.ndarray:
        """Rows whose behavior raised the `_failed` error lane.
        Drains the dispatch pipeline first (see read_state)."""
        from .step import fault_failed_rows
        self.block_until_ready()
        return fault_failed_rows(self.state)

    def restart_rows(self, ids,
                     init_state: Optional[Dict[str, Any]] = None) -> None:
        """Host-mediated restart-with-reset-state (see BatchedSystem)."""
        from .step import fault_restart_rows
        self.state = fault_restart_rows(self.state, ids, init_state)

    def clear_failed(self, ids) -> None:
        from .step import fault_clear_failed
        self.state = fault_clear_failed(self.state, ids)

    # ---------------------------------------------- in-graph supervision
    @property
    def supervision_counts(self) -> Dict[str, int]:
        """Aggregate in-graph supervision counters summed over shards
        (see BatchedSystem.supervision_counts)."""
        return counts_dict(self.sup_counts)

    def any_escalated(self) -> bool:
        """ONE device scalar: did any supervised lane escalate?"""
        if "_escalated" not in self.state:
            return False
        return bool(jax.device_get(jnp.any(self.state["_escalated"])))

    def escalated_rows(self) -> np.ndarray:
        """Global ids of escalated lanes awaiting host resolution."""
        if "_escalated" not in self.state:
            return np.empty((0,), np.int32)
        flags = np.asarray(jax.device_get(self.state["_escalated"]))
        return np.nonzero(flags)[0].astype(np.int32)

    def stop_block(self, ids) -> None:
        """Mark rows dead (no free-list on the sharded runtime: spawn is
        contiguous; rebalancing owns row placement)."""
        arr = np.unique(np.atleast_1d(np.asarray(ids, np.int32)))
        self.alive = self.alive.at[jnp.asarray(arr)].set(False)

    @property
    def total_dropped(self) -> int:
        return int(jnp.sum(self.dropped))

    @property
    def mailbox_overflow(self) -> int:
        return int(jnp.sum(self.mail_dropped))

    @property
    def dropped_per_shard(self) -> np.ndarray:
        """[n_shards] cumulative exchange-overflow counts (host copy)."""
        return np.asarray(jax.device_get(self.dropped), np.int64)

    @property
    def mailbox_overflow_per_shard(self) -> np.ndarray:
        """[n_shards] cumulative mailbox-overflow counts (host copy)."""
        return np.asarray(jax.device_get(self.mail_dropped), np.int64)

    def block_until_ready(self) -> None:
        self._host.wait(self.step_count)

    def read_mesh_stats(self) -> Dict[str, int]:
        """`spilled`, `spill_high_water`, `exchange_high_water` over the
        mesh, from the per-shard rows of `mesh_stats`: `spilled` summed (the
        int32 counters read modulo 2^32), the two high waters the largest
        any ONE shard saw, since a spill region and a pair chunk are
        provisioned a shard. What passes `spill_capacity` in one step on a
        shard is lost and `mailbox_overflow` counts it; what passes
        `pair_cap` in one pair chunk likewise, in `total_dropped`, and
        `exchange_high_water` then reads above `pair_cap`: what the chunk
        was asked to carry. All 0 on a system that keeps no `mesh_stats`
        (reduce delivery behind a lossless exchange)."""
        if self.mesh_stats is None:
            return {"spilled": 0, "spill_high_water": 0,
                    "exchange_high_water": 0}
        with self._host.read("spill"):
            self.block_until_ready()
            rows = np.asarray(jax.device_get(self.mesh_stats), np.int64)
        self._stats_read = {
            "spilled": int((rows[:, SPILLED] % (1 << 32)).sum() % (1 << 32)),
            "spill_high_water": int(rows[:, SPILL_HIGH].max()),
            "exchange_high_water": int(rows[:, EXCHANGE_HIGH].max())}
        return dict(self._stats_read)

    def read_spill(self) -> Tuple[int, int]:
        """(`spilled`, `spill_high_water`), as `BatchedSystem.read_spill`
        gives them: see `read_mesh_stats`."""
        stats = self.read_mesh_stats()
        return stats["spilled"], stats["spill_high_water"]

    @property
    def exchange_high_water(self) -> int:
        return self.read_mesh_stats()["exchange_high_water"]

    def host_stats(self) -> Dict[str, Any]:
        """The host side of this driver (HostSide.host_stats): dispatches
        and their percentiles, `starved`, compiles; and, where the system
        keeps `mesh_stats`, its three numbers as the host LAST read them
        (`read_mesh_stats`, `read_spill`, `drain_metrics`): a scrape makes
        no sync."""
        return {**self._host.host_stats(), **self._stats_read}

    # ------------------------------------------------------- telemetry plane
    def metrics_epoch_value(self) -> int:
        """ONE scalar device_get of the metrics-epoch word (the slab's
        running sum, recomputed outside the donated carry each run). Also
        syncs the newest dispatched run, like read_attention."""
        return int(jax.device_get(self.metrics_epoch))

    def read_metrics(self) -> Dict[str, np.ndarray]:
        """Host copy of the metric slab as named lanes (shards summed) —
        see metrics_slab.slab_dict. Drains the pipeline first."""
        self.block_until_ready()
        return slab_dict(self.metrics)

    def drain_metrics(self):
        """Cheap conditional drain for the bridge pump's busy→idle edge:
        returns (step, lanes) when the slab changed since the last drain,
        None otherwise — the quiet path costs one scalar fetch."""
        if not self.metrics_on:
            return None
        with self._host.read("metrics"):
            epoch = self.metrics_epoch_value()
            if epoch == self._metrics_seen_epoch:
                return None
            self._metrics_seen_epoch = epoch
            step = int(np.asarray(jax.device_get(self.step_count)))
            lanes = slab_dict(self.metrics)
            if self.mesh_stats is not None:
                # beside the histograms, as BatchedSystem's drain: the
                # registry shows each lane's total as a gauge `device_<lane>`
                stats = self.read_mesh_stats()
                if self.spill_cap == 0:
                    del stats["spilled"], stats["spill_high_water"]
                lanes.update((k, np.asarray([v], np.int64))
                             for k, v in stats.items())
            return step, lanes

    # ------------------------------------------------- checkpoint / recovery
    def checkpoint(self, directory: str, keep: Optional[int] = None,
                   compact: bool = True) -> str:
        """Checkpoint barrier (see BatchedSystem.checkpoint): quiesce on
        the non-donated step_count, snapshot the schema-v3 slab pytree
        (slab_snapshot host-gathers the mesh-sharded slabs), compact the
        attached tell journal, GC retained snapshots. `compact=False`
        defers the fsync'd journal rewrite — the hot re-shard path
        (sentinel.scale_to) compacts AFTER the pipeline resumes so the
        rewrite never sits inside the measured pause."""
        from ..persistence.slab_snapshot import gc_slabs, save_slabs
        self.block_until_ready()
        path = save_slabs(self, directory)
        if self.tell_journal is not None and compact:
            self.tell_journal.compact(self._host_step)
        if keep is not None:
            gc_slabs(directory, keep)
        return path

    def restore(self, path: str, journal=None) -> int:
        """Crash recovery, including after a preemption that changed the
        device count: when the snapshot's shard layout matches this mesh
        the slabs restore in place; otherwise they are RE-SHARDED — row
        slabs re-placed under this mesh's sharding, per-shard counters
        conserved into shard 0, and in-flight inbox rows re-placed by
        destination shard in their original delivery order. The caller
        builds a same-capacity system and re-runs its spawns first (see
        BatchedSystem.restore). With `journal` set, journaled batches past
        the snapshot step replay to the crash frontier."""
        from ..persistence.slab_snapshot import load_slab_tree
        return self.restore_tree(load_slab_tree(path), journal=journal)

    def restore_tree(self, tree: Dict[str, Any], journal=None) -> int:
        """Restore from an already-loaded slab pytree (`slab_pytree` host
        copies). The hot re-shard path (sentinel.scale_to) takes the tree
        at the drain barrier and restores through HERE, skipping the disk
        round trip entirely — the fsync'd file write runs concurrently as
        durability, not as pause."""
        from ..persistence.slab_snapshot import restore_slab_pytree
        from ..persistence.tell_journal import replay_journal
        snap_rows = int(np.asarray(tree["behavior_id"]).shape[0])
        if snap_rows != self.capacity:
            raise ValueError(f"snapshot capacity {snap_rows} != "
                             f"system capacity {self.capacity}")
        if tuple(np.asarray(tree["inbox_dst"]).shape) == \
                tuple(self.inbox_dst.shape):
            restore_slab_pytree(self, tree)
            # re-arm the drain gate against the restored slab (the
            # resharded path recomputes the epoch itself)
            self.metrics_epoch = jax.device_put(
                jnp.asarray(int(np.asarray(
                    jax.device_get(self.metrics)).sum()), jnp.int32),
                NamedSharding(self.mesh, P()))
        else:
            self._restore_resharded(tree)
        self._host_step = int(np.asarray(jax.device_get(self.step_count)))
        self._metrics_seen_epoch = 0  # next drain re-ingests the slab
        with self._lock:
            self._host_staged = []
        if journal is not None:
            replay_journal(self, journal)
        return self._host_step

    def _restore_resharded(self, tree: Dict[str, Any]) -> None:
        """Re-shard a snapshot taken on a different device count onto this
        mesh. Row-indexed slabs ([capacity] and [capacity, ...]) are layout
        independent — fresh device_puts under this mesh's sharding place
        them. Per-shard aggregates ([old_n_shards]) are conserved by
        summing into shard 0 (only totals are ever read). In-flight inbox
        rows are gathered on the host and re-placed into each destination
        shard's block starting at the exchange region, preserving global
        order — the stable (recipient, slot) delivery sort then delivers
        them in the original order on the first restored step."""
        from ..persistence.slab_snapshot import SCHEMA_VERSION
        version = int(np.asarray(tree.get("schema_version", 1)))
        if version > SCHEMA_VERSION:
            raise ValueError(
                f"snapshot schema v{version} is newer than this runtime's "
                f"v{SCHEMA_VERSION}; upgrade the runtime to restore it")
        shard = NamedSharding(self.mesh, P(self.axis))
        repl = NamedSharding(self.mesh, P())
        for col, arr in tree["state"].items():
            cur = self.state.get(col)
            if cur is None:
                continue
            if tuple(cur.shape) != tuple(np.asarray(arr).shape):
                raise ValueError(
                    f"slab shape mismatch for state[{col!r}]: "
                    f"{np.asarray(arr).shape} vs {tuple(cur.shape)}")
            self.state[col] = jax.device_put(jnp.asarray(arr), shard)
        for col, cur in list(self.state.items()):
            if col not in tree["state"]:
                # v1 upgrade: absent columns reset to their re-arm fill
                self.state[col] = jax.device_put(
                    jnp.full(cur.shape, reserved_fill(col), cur.dtype),
                    shard)
        self.behavior_id = jax.device_put(
            jnp.asarray(tree["behavior_id"], jnp.int32), shard)
        self.alive = jax.device_put(
            jnp.asarray(tree["alive"], jnp.bool_), shard)
        self.step_count = jax.device_put(
            jnp.asarray(np.asarray(tree["step_count"]).max(), jnp.int32),
            repl)
        ns = self.n_shards
        # attention words are a per-shard summary of the carry: conserve
        # them like the other per-shard aggregates (flags OR, counters sum
        # into row 0, step/progress max) rather than copying a [old_ns, W]
        # block that no longer matches this mesh. Rows beyond 0 re-fill on
        # the first restored step.
        att_rows = np.zeros((ns, ATT_WORDS), np.int32)
        self._overflow_reported = np.zeros((ns, 2), np.int64)
        att = tree.get("attention")
        if att is not None:
            old = decode_attention(np.asarray(att))
            att_rows[0] = (old["flags"], old["mail_dropped"],
                           old["dead_letters"], old["step"],
                           old["exchange_dropped"], old["step"])
            self._overflow_reported[0] = (old["mail_dropped"],
                                          old["exchange_dropped"])
        self.attention = jax.device_put(jnp.asarray(att_rows), shard)
        dropped = np.zeros((ns,), np.int32)
        dropped[0] = int(np.asarray(tree.get("dropped", 0)).sum())
        self.dropped = jax.device_put(jnp.asarray(dropped), shard)
        md = np.zeros((ns,), np.int32)
        md[0] = int(np.asarray(tree.get("mail_dropped", 0)).sum())
        self.mail_dropped = jax.device_put(jnp.asarray(md), shard)
        if self.mesh_stats is not None:
            # conserved into shard 0 like the counters above: what was
            # summed on read is summed, what was the largest stays so
            ms = np.zeros((ns, 3), np.int32)
            old = np.asarray(tree.get("mesh_stats", ms), np.int32)
            ms[0] = (old[:, SPILLED].sum(dtype=np.int32),  # wraps, as it did
                     old[:, SPILL_HIGH].max(), old[:, EXCHANGE_HIGH].max())
            self.mesh_stats = jax.device_put(jnp.asarray(ms), shard)
        sc = np.zeros((ns, N_COUNTERS), np.int32)
        if "sup_counts" in tree:
            sc[0] = np.asarray(tree["sup_counts"]).reshape(
                -1, N_COUNTERS).sum(axis=0)
        self.sup_counts = jax.device_put(jnp.asarray(sc), shard)
        # metric slab: conserve histogram counts into row 0, like the
        # other per-shard aggregates (only totals are ever read)
        mt = np.zeros((ns, N_HIST, N_BUCKETS), np.int32)
        if "metrics" in tree:
            mt[0] = np.asarray(tree["metrics"]).reshape(
                -1, N_HIST, N_BUCKETS).sum(axis=0)
        self.metrics = jax.device_put(jnp.asarray(mt), shard)
        self.metrics_epoch = jax.device_put(
            jnp.asarray(int(mt.sum()), jnp.int32), repl)
        self._metrics_seen_epoch = 0
        # in-flight mail: gather valid rows, re-place by destination shard
        dst = np.asarray(tree["inbox_dst"])
        typ = np.asarray(tree["inbox_type"])
        pl = np.asarray(tree["inbox_payload"])
        val = np.asarray(tree["inbox_valid"]).astype(bool)
        if pl.shape[1] != self.payload_width:
            raise ValueError(f"snapshot payload width {pl.shape[1]} != "
                             f"system payload width {self.payload_width}")
        m_global = self.m_local * ns
        np_dtype = np.dtype(jnp.dtype(self.payload_dtype))
        new_dst = np.full((m_global,), -1, np.int32)
        new_typ = np.zeros((m_global,), np.int32)
        new_pl = np.zeros((m_global, self.payload_width), np_dtype)
        new_val = np.zeros((m_global,), np.bool_)
        region = self.m_local - self.spill_cap
        used = np.zeros((ns,), np.int64)
        for i in np.nonzero(val)[0]:
            d = int(dst[i])
            s = max(0, min(d, self.capacity - 1)) // self.local_n
            u = int(used[s])
            if u >= region:
                raise RuntimeError(
                    f"in-flight mail for shard {s} ({u + 1} rows) exceeds "
                    f"its inbox block on the {ns}-shard mesh")
            slot = s * self.m_local + self.spill_cap + u
            new_dst[slot] = d
            new_typ[slot] = int(typ[i])
            new_pl[slot] = pl[i]
            new_val[slot] = True
            used[s] += 1
        self.inbox_dst = jax.device_put(jnp.asarray(new_dst), shard)
        self.inbox_type = jax.device_put(jnp.asarray(new_typ), shard)
        self.inbox_payload = jax.device_put(
            jnp.asarray(new_pl, self.payload_dtype), shard)
        self.inbox_valid = jax.device_put(jnp.asarray(new_val), shard)
        if self.metrics_on:
            # enqueue stamps don't survive a re-shard positionally: re-arm
            # every re-placed row at the restored step (age restarts, same
            # rule as the exchange re-stamp)
            restored = int(np.asarray(tree["step_count"]).max())
            enq = np.where(new_val, restored, 0).astype(np.int32)
            self.inbox_enq = jax.device_put(jnp.asarray(enq), shard)
