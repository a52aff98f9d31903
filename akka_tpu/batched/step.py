"""StepCore: the shared deliver→update kernel of the batched device runtime.

One implementation backs both BatchedSystem (single device) and
ShardedBatchedSystem (mesh): deliver the step's messages into per-actor
inboxes, run every live actor's behavior as one vmapped lax.switch, and hand
the emitted messages back to the caller (who rebuilds the local inbox or
routes them across shards).

This is the tensorized form of the reference's hot loop (SURVEY.md §3.2):
Mailbox.processMailbox (dispatch/Mailbox.scala:260-277) + ActorCell.invoke
(actor/ActorCell.scala:539-555) + the typed interpreter's tag switch
(typed/Behavior.scala:244-278).

Two delivery modes:
- reduce: one segment reduction -> Inbox(sum, max, count). Commutative
  fast path; supports StaticTopology compiled routing.
- slots:  stable (recipient, seq) sort -> per-actor Mailbox of up to S
  discrete (type, payload) messages in per-sender FIFO order — the full
  Akka envelope-mailbox contract for non-commutative behaviors.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops.prefix import prefix_count
from ..ops.segment import (Delivery, SlotDelivery, check_delivery_backend,
                           deliver, deliver_slots, deliver_static)
from .behavior import BatchedBehavior, Ctx, Emit, Inbox, Mailbox, _bshape
from .supervision import (N_COUNTERS, SupervisionTables, apply_supervision,
                          pack_attention, reserved_fill)


# The layers of the step program, as `jax.named_scope` names: every device
# operation carries the scope it was traced under in its metadata, and the
# benchmark's scope table (benchmark/xscope.py) sums a traced run's device
# time by the first `akka.` component of that path. Sub-blocks nest as
# `akka.<layer>.<block>`. A scope changes metadata only, never the program.
SCOPE_LAYERS = ("akka.deliver", "akka.behavior", "akka.supervision",
                "akka.emit", "akka.exchange", "akka.metrics",
                "akka.attention", "akka.route")


class StepCore:
    """Builds the jit-safe deliver+update function shared by both runtimes.

    n_local: actors owned by this caller (rows in the state slabs it passes);
    n_global: total actor-id space (== n_local on a single device).
    slots=0 selects reduce mode; slots>0 selects per-message mailboxes of S
    slots each.
    """

    def __init__(self, behaviors: Sequence[BatchedBehavior], n_local: int,
                 payload_width: int, out_degree: int, payload_dtype,
                 slots: int = 0, need_max: bool = False, topology=None,
                 delivery: str = "auto", n_global: Optional[int] = None,
                 spill_cap: int = 0,
                 delivery_backend: Optional[str] = None,
                 attention_latch_col: Optional[str] = None,
                 routers: Sequence[Any] = ()):
        self.behaviors = list(behaviors)
        self.n_local = int(n_local)
        self.n_global = int(n_global if n_global is not None else n_local)
        self.payload_width = int(payload_width)
        self.out_degree = int(out_degree)
        self.payload_dtype = payload_dtype
        self.slots = int(slots)
        self.need_max = need_max
        self.topology = topology
        self.delivery = delivery
        # kernel family of slots delivery and of the mesh runtime's
        # exchange bucketing (ops/segment.py): None/"auto" = by platform,
        # "xla" = rank-then-scatter, "reference" = the wide-sort kernels
        check_delivery_backend(delivery_backend)
        self.delivery_backend = delivery_backend
        # spill region size (slots mode): overflow + suspended-row mail is
        # retained there instead of dropped (unbounded-mailbox semantics)
        self.spill_cap = int(spill_cap)
        # state column whose any() feeds ATT_LATCH_BIT of the host-attention
        # word (the bridge passes its promise-replied column; None = no
        # latch bit in the word)
        self.attention_latch_col = attention_latch_col
        # pool routers (routing/batched.py BatchedRouter): with none, the
        # route stage is not in the program at all
        self.routers = tuple(routers)
        if self.routers and topology is not None:
            raise ValueError("a router readdresses messages inside the "
                             "step; StaticTopology compiles the addresses "
                             "away: use dynamic delivery")
        # the scopes are metadata, which JAX leaves out of the persistent
        # compile cache's key unless told otherwise: an executable cached
        # before a scope was added or renamed would be loaded with the names
        # it was compiled with, and a traced run would sum them under the
        # wrong layer or none. With metadata in the key, a step program whose
        # names (or source lines) changed compiles anew instead.
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)

        if self.slots == 0:
            bad = [b.name for b in self.behaviors if b.inbox == "slots"]
            if bad:
                raise ValueError(
                    f"behaviors {bad} need per-message mailboxes: construct "
                    f"the system with mailbox_slots > 0")
        if self.slots > 0 and topology is not None:
            raise ValueError("StaticTopology routing is a reduce-mode "
                             "optimization; slots mode uses dynamic delivery")
        # in-graph supervision tables (batched/supervision.py): trace-time
        # [n_behaviors] parameter rows; sup.active == False keeps the whole
        # supervision pass out of the program entirely
        self.sup = SupervisionTables(self.behaviors)
        self._branches = [self._wrap(b) for b in self.behaviors]
        # which behaviors consume ordered slots: overflow past the slot cap
        # is a real drop only for these — reduce-kind recipients get every
        # message through the exact aggregation, so counting them would
        # report phantom loss
        self._slots_kind = jnp.asarray([b.inbox == "slots"
                                        for b in self.behaviors], jnp.bool_)

    # ---------------------------------------------------------------- wrap
    def _wrap(self, b: BatchedBehavior):
        """Uniform branch signature for lax.switch across inbox kinds, with
        activity gating (idle actors skip: no mailbox -> no state change,
        mirroring an empty mailbox never scheduling, Dispatcher.scala:120-143)
        and alive gating applied by the caller's per_actor."""
        slots_mode = self.slots > 0
        # a block per behavior under the layer, so a traced run's scope
        # table tells the branches of the switch apart. JAX renders the
        # first scope inside a transform as `vmap(<name>)`, which no reader
        # takes for a component of the path: `row` takes that place, and
        # the block's name stays whole (.../akka.behavior/vmap(row)/
        # akka.behavior.<name>/...)
        block = "akka.behavior." + re.sub(r"[^A-Za-z0-9_\-]", "_", b.name)

        @jax.named_scope("row")
        @jax.named_scope(block)
        def branch(state_row, delivered, ctx: Ctx):
            if slots_mode:
                mailbox: Mailbox = delivered
                if b.inbox == "slots":
                    new_cols, emit = b.receive(dict(state_row), mailbox, ctx)
                else:
                    new_cols, emit = b.receive(dict(state_row),
                                               mailbox.reduce(), ctx)
                count = mailbox.count
            else:
                inbox: Inbox = delivered
                new_cols, emit = b.receive(dict(state_row), inbox, ctx)
                count = inbox.count
            emit = emit.with_type()
            merged = dict(state_row)
            merged.update(new_cols)
            active = (count > 0) | jnp.asarray(b.always_on)
            merged = jax.tree.map(
                lambda new, old: jnp.where(_bshape(active, new), new, old),
                merged, dict(state_row))
            if b.nonfinite_guard:
                # opt-in non-finite guard: a new state row carrying NaN/Inf
                # marks the lane failed — the update layer then DISCARDS it
                # (pre-failure state retained, like any failing receive)
                # instead of the NaN poisoning every subsequent reduce
                bad = jnp.asarray(False)
                for v in new_cols.values():
                    if jnp.issubdtype(jnp.asarray(v).dtype, jnp.inexact):
                        bad = bad | jnp.any(~jnp.isfinite(v))
                merged["_failed"] = merged.get(
                    "_failed", jnp.asarray(False)) | (bad & active)
            emit = Emit(dst=jnp.where(active, emit.dst, -1),
                        payload=emit.payload,
                        valid=emit.valid & active,
                        type=emit.type)
            return merged, emit

        return branch

    # --------------------------------------------------------------- route
    def route(self, state, inbox_dst, inbox_valid):
        """The route stage, ahead of delivery: every message addressed to a
        router's row is readdressed to a routee of its pool.

        Message k of the step's messages to the router, k counted in INBOX
        ROW ORDER (retained spill, then the emission slots by actor row,
        then the host's tells: the order benchmark/reference/router.py
        reproduces), takes the sequence number `next + k` and goes to
        `routee_base + index_of(next + k)`; then `next <- (next + count)
        mod n_routees` and `routed += count` (int32, modulo 2^32) in the
        router's own row of the state. The rank is one exclusive prefix
        count over the inbox: a property of the whole batch, which no
        vmapped per-actor behavior can compute. Returns (state, inbox_dst).
        """
        with jax.named_scope("akka.route"):
            state = dict(state)
            for pool in self.routers:
                hit = inbox_valid & (inbox_dst == pool.row)
                with jax.named_scope("akka.route.rank"):
                    # not a cumsum: the TPU compiler's reduce-windows carry
                    # no path, and this layer has a metric (ops/prefix.py)
                    upto = prefix_count(hit)
                    count = upto[-1]
                with jax.named_scope("akka.route.readdress"):
                    nxt = state["next"][pool.row].astype(jnp.uint32)
                    seq = nxt + upto - hit.astype(jnp.uint32)
                    inbox_dst = jnp.where(
                        hit, pool.routee_base + pool.index_of(seq),
                        inbox_dst)
                    state["next"] = state["next"].at[pool.row].set(
                        ((nxt + count) % jnp.uint32(pool.n_routees)
                         ).astype(jnp.int32))
                    state["routed"] = state["routed"].at[pool.row].add(
                        count.astype(jnp.int32))
        return state, inbox_dst

    # ------------------------------------------------------------- deliver
    def deliver(self, inbox_dst, inbox_type, inbox_payload, inbox_valid,
                topo_arrays=(), dst_offset=None, slots_kind_row=None,
                suspended=None):
        """Route this step's messages into per-actor inboxes. dst_offset
        (traced scalar) maps global recipient ids to local rows (sharded
        callers pass shard_base; single-device callers pass None)."""
        with jax.named_scope("akka.deliver"):
            n = self.n_local
            dst = inbox_dst if dst_offset is None else inbox_dst - dst_offset
            if self.slots > 0:
                return deliver_slots(dst, inbox_type, inbox_payload,
                                     inbox_valid, n, self.slots,
                                     self.need_max,
                                     spill_cap=self.spill_cap,
                                     slots_kind=slots_kind_row,
                                     suspended=suspended,
                                     backend=self.delivery_backend)
            if self.topology is not None:
                nk = self.n_local * self.out_degree
                d = deliver_static(self.topology, topo_arrays,
                                   inbox_payload[:nk], inbox_valid[:nk],
                                   self.need_max)
                if inbox_dst.shape[0] > nk:
                    # host-injected tail: a SMALL scatter, and only when any
                    # tail row is live — in a run(n) scan the tail is consumed
                    # on the first step, so steady-state steps skip the whole
                    # delivery at runtime (lax.cond, not select)
                    tail_d, tail_p, tail_v = (dst[nk:], inbox_payload[nk:],
                                              inbox_valid[nk:])

                    def with_tail(op):
                        td, tp, tv = op
                        hd = deliver(td, tp, tv, n, self.need_max,
                                     mode="scatter")
                        return Delivery(sum=d.sum + hd.sum,
                                        max=jnp.maximum(d.max, hd.max),
                                        count=d.count + hd.count)

                    def no_tail(op):
                        return d

                    d = jax.lax.cond(jnp.any(tail_v), with_tail, no_tail,
                                     (tail_d, tail_p, tail_v))
                return d
            return deliver(dst, inbox_payload, inbox_valid, n, self.need_max,
                           mode=self.delivery)

    # -------------------------------------------------------------- update
    def update(self, state, behavior_id, alive, delivered, step_count,
               id_base=0, tables=()):
        """Vmapped behavior switch over all local rows, then the in-graph
        supervision pass. Returns (new_state, new_behavior_id, new_alive,
        emits, sup_delta) with emits shaped [n_local, K(...)] and sup_delta
        the [N_COUNTERS] int32 directive/dead-letter counter increment
        (zeros when no behavior carries a supervisor). Dead rows neither
        update nor emit; STOP-directive lanes come back dead in
        new_alive."""
        n = self.n_local
        branches = self._branches
        ids = jnp.asarray(id_base, jnp.int32) + jnp.arange(n, dtype=jnp.int32)
        n_global = jnp.asarray(self.n_global, jnp.int32)

        if self.slots > 0:
            d: SlotDelivery = delivered
            per_actor_inbox = (d.types, d.payload, d.valid, d.count, d.sum,
                               d.max)

            def make_inbox(t, pl, v, c, s, mx):
                return Mailbox(types=t, payload=pl, valid=v, count=c, sum=s,
                               max=mx)
        else:
            d = delivered
            per_actor_inbox = (d.sum, d.max, d.count)

            def make_inbox(s, mx, c):
                return Inbox(sum=s, max=mx, count=c)

        def per_actor(state_row, b_id, alive_i, gid, *inbox_parts):
            inbox = make_inbox(*inbox_parts)
            # `tables` is closed over, not vmapped: every lane sees the
            # same small lookup arrays (placement tables etc.)
            ctx = Ctx(actor_id=gid, step=step_count, n_actors=n_global,
                      tables=tables)
            # an already-failed row is suspended: no update, no emissions,
            # until the host restarts it (FaultHandling.suspend parity —
            # actor/dungeon/FaultHandling.scala). In slots mode with a spill
            # region its mail is RETAINED (spilled, redelivered after
            # restart — the reference's queued-while-suspended semantics);
            # in reduce mode / spill_cap == 0 it is dropped (deviation)
            was_failed = state_row.get("_failed", jnp.asarray(False))
            live = alive_i & ~was_failed
            new_state, emit = jax.lax.switch(b_id, branches, state_row,
                                             inbox, ctx)
            # a row FAILING THIS STEP keeps its pre-failure state (the
            # aborted receive must not half-apply) and emits nothing; only
            # the flag itself sticks (handleInvokeFailure: the failing
            # message's effects are discarded, the failure is recorded)
            now_failed = new_state.get("_failed", jnp.asarray(False))
            apply = live & ~now_failed
            merged = jax.tree.map(
                lambda new, old: jnp.where(_bshape(apply, new), new, old),
                new_state, state_row)
            if "_failed" in merged:
                merged["_failed"] = jnp.where(live, now_failed, was_failed)
            emit = Emit(dst=jnp.where(apply, emit.dst, -1),
                        payload=emit.payload,
                        valid=emit.valid & apply,
                        type=emit.type)
            return merged, emit

        with jax.named_scope("akka.behavior"):
            new_state, emits = jax.vmap(per_actor)(state, behavior_id, alive,
                                                   ids, *per_actor_inbox)
            # device-side become (ActorCell.become :589-602): behaviors
            # write the target behavior index into the reserved `_become`
            # column; the runtime applies it and re-arms the column to -1
            if "_become" in new_state:
                req = new_state["_become"]
                new_behavior_id = jnp.where(req >= 0, req.astype(jnp.int32),
                                            behavior_id)
                new_state = dict(new_state)
                new_state["_become"] = jnp.full_like(req, -1)
            else:
                new_behavior_id = behavior_id
        # in-graph supervision: resolve this step's fresh failures (and any
        # backoff restarts coming due) as masked lane ops — no host poll.
        # Table lookups use the PRE-become behavior id: the failure happened
        # under the behavior that was running when it was detected.
        new_alive = alive
        sup_delta = jnp.zeros((N_COUNTERS,), jnp.int32)
        if self.sup.active and "_failed" in new_state:
            with jax.named_scope("akka.supervision"):
                new_state, new_alive, sup_delta = apply_supervision(
                    self.sup, new_state, behavior_id, alive,
                    old_failed=state["_failed"], delivered_count=d.count,
                    step=step_count)
        return new_state, new_behavior_id, new_alive, emits, sup_delta

    def attention_word(self, state, mail_dropped, sup_counts, step_count,
                       exch_dropped=None):
        """[ATT_WORDS] int32 host-attention word for the step that produced
        these carries (supervision.pack_attention over this core's latch
        column). Emitted as a NON-donated output of the jitted step so a
        `device_get` on it doubles as the pipeline sync for the program —
        the depth-k pump reads this instead of `block_until_ready` plus
        wide per-column fetches. Accepts scalar or per-shard blocks for
        mail_dropped / sup_counts (shard_map callers pass their local
        blocks and reshape the result to [1, ATT_WORDS], yielding the
        per-shard word whose counter/progress lanes feed the sentinel);
        `exch_dropped` is the caller's exchange-overflow aggregate."""
        with jax.named_scope("akka.attention"):
            return pack_attention(state, mail_dropped, sup_counts,
                                  step_count,
                                  latch_col=self.attention_latch_col,
                                  exch_dropped=exch_dropped)

    def run_local(self, state, behavior_id, alive, inbox_dst, inbox_type,
                  inbox_payload, inbox_valid, step_count, topo_arrays=(),
                  dst_offset=None, id_base=0, tables=()):
        """deliver + update in one call. Returns (new_state, new_behavior_id,
        new_alive, emits, dropped, spill, sup_delta, delivered_count) where
        dropped is this step's REAL message-loss count (0 in reduce mode —
        reductions never overflow; spill-region overflow in slots mode),
        spill is a (dst, type, payload, valid) tuple of retained mail to
        re-inject at the FRONT of the next inbox (spill dst is GLOBAL —
        dst_offset re-applied), or None when spill_cap == 0, sup_delta is
        the [N_COUNTERS] supervision counter increment, and delivered_count
        is the [n_local] int32 per-lane delivery count of this step — the
        mailbox-occupancy sample the metric slab histograms
        (batched/metrics_slab.py; free either way, the delivery kernel
        already computes it)."""
        if self.routers:
            state, inbox_dst = self.route(state, inbox_dst, inbox_valid)
        slots_kind_row = suspended = None
        if self.slots > 0 and self.spill_cap > 0:
            slots_kind_row = self._slots_kind[behavior_id]
            if "_failed" in state:
                # suspended = failed-but-restartable; dead rows' mail is
                # discarded as before (no resurrection to wait for).
                # Supervised lanes are EXCLUDED: their down-time mail is
                # dead-lettered by the supervision pass (backoff contract),
                # not retained for the next incarnation
                suspended = state["_failed"] & alive
                if self.sup.active:
                    suspended = suspended & ~self.sup.enabled[behavior_id]
        d = self.deliver(inbox_dst, inbox_type, inbox_payload, inbox_valid,
                         topo_arrays, dst_offset, slots_kind_row, suspended)
        new_state, new_behavior_id, alive, emits, sup_delta = self.update(
            state, behavior_id, alive, d, step_count, id_base, tables)
        spill = None
        if self.slots > 0 and self.spill_cap > 0:
            sd = d.spill_dst
            if dst_offset is not None:
                sd = jnp.where(d.spill_valid, sd + dst_offset, -1)
            spill = (sd, d.spill_type, d.spill_payload, d.spill_valid)
            dropped = d.dropped
        elif self.slots > 0:
            # bounded mailbox: per-recipient overflow, masked to slots-kind
            # recipients (reduce-kind consume everything via aggregation)
            over = jnp.maximum(d.count - self.slots, 0)
            dropped = jnp.sum(jnp.where(self._slots_kind[behavior_id],
                                        over, 0)).astype(jnp.int32)
        else:
            dropped = jnp.asarray(0, jnp.int32)
        return (new_state, new_behavior_id, alive, emits, dropped, spill,
                sup_delta, d.count)


# -------------------------------------------------- shared fault handling
# Host-side error-lane helpers used by BOTH BatchedSystem and
# ShardedBatchedSystem (the same dedup role StepCore plays for the step).

def fault_any_failed(state) -> bool:
    """Cheap check: ONE device scalar, not the whole column — the pump
    calls this every tick."""
    if "_failed" not in state:
        return False
    import jax as _jax
    return bool(_jax.device_get(jnp.any(state["_failed"])))


def fault_failed_rows(state):
    import numpy as _np
    import jax as _jax
    if "_failed" not in state:
        return _np.empty((0,), _np.int32)
    flags = _np.asarray(_jax.device_get(state["_failed"]))
    return _np.nonzero(flags)[0].astype(_np.int32)


def fault_restart_rows(state, ids, init_state=None):
    """Restart-with-reset-state: zero the rows' columns (reserved columns
    re-armed), returning the new state dict. Mutates nothing. The device
    incarnation counter `_gen` is PRESERVED AND BUMPED, not zeroed — a
    host restart is a new incarnation just like an in-graph one."""
    import numpy as _np
    idx = jnp.asarray(_np.atleast_1d(_np.asarray(ids, _np.int32)))
    out = dict(state)
    for col, arr in out.items():
        if col == "_gen":
            out[col] = arr.at[idx].add(jnp.asarray(1, arr.dtype))
            continue
        out[col] = arr.at[idx].set(
            jnp.asarray(reserved_fill(col), arr.dtype))
    if init_state:
        for col, value in init_state.items():
            out[col] = out[col].at[idx].set(
                jnp.asarray(value, out[col].dtype))
    return out


def fault_clear_failed(state, ids):
    """Clear only the failure flag (used by the 'stop' policy so a dead
    row stops re-reporting). Also lowers `_escalated` — the host clearing
    a lane IS the escalation's resolution."""
    import numpy as _np
    if "_failed" not in state:
        return state
    idx = jnp.asarray(_np.atleast_1d(_np.asarray(ids, _np.int32)))
    out = dict(state)
    out["_failed"] = out["_failed"].at[idx].set(False)
    if "_escalated" in out:
        out["_escalated"] = out["_escalated"].at[idx].set(False)
    return out
