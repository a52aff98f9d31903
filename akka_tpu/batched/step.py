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
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.prefix import prefix_count
from ..ops.segment import (Delivery, check_delivery_backend, deliver,
                           deliver_slots, deliver_static)
from .behavior import (BatchedBehavior, Ctx, Emit, Folded, Inbox, Mailbox,
                       _bshape)
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


class SlotEmits(NamedTuple):
    """A step's emissions where a slots behavior declares an emission
    width (`StepCore.emit_width`): every row's, one a turn of its fold,
    not yet packed, and the step's counts."""

    emit: Emit          # [N, raw_width] (payload [N, raw_width, P])
    counts: jax.Array   # [3] int32: stashed, lost by a full stash, retained


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
                 routers: Sequence[Any] = (),
                 emit_rows: Optional[int] = None):
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
        # the emitting path (`SlotEmits`), compiled in only where a slots
        # behavior declares an emission width: rows [0, emit_rows) take
        # `emit_width` emission rows each, the rest `out_degree`
        self.emit_width = max((b.emit_width for b in self.behaviors),
                              default=0)
        self.emit_rows = (self.n_local if emit_rows is None
                          else min(int(emit_rows), self.n_local))
        self.raw_width = self.out_degree
        if self.emit_width:
            bad = [b.name for b in self.behaviors
                   if b.emit_width and b.inbox != "slots"]
            if bad:
                raise ValueError(f"behaviors {bad}: an emission width is a "
                                 f"slots behavior's")
            if self.spill_cap <= 0:
                raise ValueError("an emission budget retains mail: give the "
                                 "system a spill region")
            if self.out_degree > self.emit_width:
                raise ValueError(f"out_degree {self.out_degree} is wider than "
                                 f"the emission width {self.emit_width}")
            # a fold's turns: its mailbox's slots and the stash's replays
            self.raw_width = max(self.out_degree, self.slots + self.emit_width)
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
        # the slots extent (`slots_extent`): one past the highest local row
        # the host gave a slots behavior (`note_rows`; 0: none yet), and
        # the extent the last step program traced took
        self._slots_top = 0
        self.compiled_extent: Optional[int] = None

    # ---------------------------------------------------------------- wrap
    def _wrap(self, b: BatchedBehavior, suffix: str = "",
              written: Optional[set] = None):
        """Uniform branch signature for lax.switch across inbox kinds, with
        activity gating (idle actors skip: no mailbox -> no state change,
        mirroring an empty mailbox never scheduling, Dispatcher.scala:120-143)
        and alive gating applied by the caller's per_actor. `suffix` ends
        the branch's block name; `written`, where given, collects the
        columns the branch may change as it is traced."""
        slots_mode = self.slots > 0
        # a block per behavior under the layer, so a traced run's scope
        # table tells the branches of the switch apart. JAX renders the
        # first scope inside a transform as `vmap(<name>)`, which no reader
        # takes for a component of the path: `row` takes that place, and
        # the block's name stays whole (.../akka.behavior/vmap(row)/
        # akka.behavior.<name>/...)
        block = ("akka.behavior." + re.sub(r"[^A-Za-z0-9_\-]", "_", b.name)
                 + suffix)

        emitting = self.emit_width > 0
        untouched = jnp.asarray([self.slots, 0, 0], jnp.int32)

        @jax.named_scope("row")
        @jax.named_scope(block)
        def branch(state_row, delivered, ctx: Ctx):
            counts = untouched  # slots taken, stashed, lost to the stash
            if slots_mode:
                mailbox: Mailbox = delivered
                if b.inbox == "slots" and b.emit_width:
                    new_cols, folded = b.receive(
                        dict(state_row), mailbox._replace(budget=b.emit_width),
                        ctx)
                    if not isinstance(folded, Folded):
                        raise TypeError(f"behavior {b.name!r} declares an "
                                        f"emission width: its receive returns "
                                        f"Mailbox.fold_emit's Folded")
                    emit = folded.emit
                    counts = jnp.stack([folded.taken, folded.stashed,
                                        folded.lost]).astype(jnp.int32)
                elif b.inbox == "slots":
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
            if written is not None:
                written.update(new_cols)
                if b.nonfinite_guard:
                    written.add("_failed")
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
            if not emitting:
                return merged, emit
            return (merged, _widen(emit, self.raw_width),
                    jnp.where(active, counts, untouched))

        return branch

    def _untouched(self, b: BatchedBehavior):
        """`b`'s branch for rows none of which runs `b`: the state row as
        it was, an empty emission of the real branch's width and dtype,
        the counts of a row that did not run. Nothing of `b` is traced
        into the program."""
        real = self._wrap(b)

        @jax.named_scope("row")
        def branch(state_row, delivered, ctx):
            like = jax.eval_shape(real, state_row, delivered, ctx)[1]
            emit = Emit.none(like.dst.shape[0], like.payload.shape[-1],
                             like.payload.dtype)
            if not self.emit_width:
                return dict(state_row), emit
            return (dict(state_row), emit,
                    jnp.asarray([self.slots, 0, 0], jnp.int32))

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
                suspended=None, extent=None):
        """Route this step's messages into per-actor inboxes. dst_offset
        (traced scalar) maps global recipient ids to local rows (sharded
        callers pass shard_base; single-device callers pass None).
        A slots system gets `deliver_slots`'s (delivery, place, retain),
        `extent` its slots extent (`beyond_extent`)."""
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
                                     backend=self.delivery_backend,
                                     extent=extent,
                                     retain=self.emit_width > 0)
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
               id_base=0, tables=(), extent=None, beyond=None):
        """Vmapped behavior switch over all local rows, then the in-graph
        supervision pass. Returns (new_state, new_behavior_id, new_alive,
        emits, sup_delta) with emits shaped [n_local, K(...)] and sup_delta
        the [N_COUNTERS] int32 directive/dead-letter counter increment
        (zeros when no behavior carries a supervisor). Dead rows neither
        update nor emit; STOP-directive lanes come back dead in
        new_alive. Where a behavior declares an emission width, `emits` is
        (Emit [n_local, raw_width], [n_local, 3] int32: slots taken,
        stashed, lost to a full stash).

        A slots system's `delivered` is `deliver`'s (delivery, place,
        retain), its slots read through `place`. With a slots extent R
        (`extent` and `beyond`, from `run_local`): where `beyond` is false,
        rows [0, R) run the switch and rows [R, N) one whose slots
        behaviors are `_untouched`, so that no fold runs past R; each half
        is written back into the state in place, the tail only where its
        branches write. Where it is true, the N-wide switch runs, its blocks named
        `akka.behavior.<name>.all`. Both give what the N-wide switch
        gives."""
        n = self.n_local
        ids = jnp.asarray(id_base, jnp.int32) + jnp.arange(n, dtype=jnp.int32)
        n_global = jnp.asarray(self.n_global, jnp.int32)

        if self.slots > 0:
            d, place, _ = delivered
            mail = (d.valid, d.count, d.sum, d.max)

            def make_inbox(t, pl, v, c, s, mx):
                return Mailbox(types=t, payload=pl, valid=v, count=c, sum=s,
                               max=mx)
        else:
            d = delivered

            def make_inbox(s, mx, c):
                return Inbox(sum=s, max=mx, count=c)

        def per_actor_of(branches):
            def per_actor(state_row, b_id, alive_i, gid, *inbox_parts):
                inbox = make_inbox(*inbox_parts)
                # `tables` is closed over, not vmapped: every lane sees the
                # same small lookup arrays (placement tables etc.)
                ctx = Ctx(actor_id=gid, step=step_count, n_actors=n_global,
                          tables=tables)
                # an already-failed row is suspended: no update, no
                # emissions, until the host restarts it (FaultHandling.
                # suspend parity — actor/dungeon/FaultHandling.scala). In
                # slots mode with a spill region its mail is RETAINED
                # (spilled, redelivered after restart — the reference's
                # queued-while-suspended semantics); in reduce mode /
                # spill_cap == 0 it is dropped (deviation)
                was_failed = state_row.get("_failed", jnp.asarray(False))
                live = alive_i & ~was_failed
                new_state, emit, *counts = jax.lax.switch(
                    b_id, branches, state_row, inbox, ctx)
                # a row FAILING THIS STEP keeps its pre-failure state (the
                # aborted receive must not half-apply) and emits nothing;
                # only the flag itself sticks (handleInvokeFailure: the
                # failing message's effects are discarded, the failure is
                # recorded)
                now_failed = new_state.get("_failed", jnp.asarray(False))
                apply = live & ~now_failed
                merged = jax.tree.map(
                    lambda new, old: jnp.where(_bshape(apply, new), new, old),
                    new_state, state_row)
                if "_failed" in merged:
                    merged["_failed"] = jnp.where(live, now_failed,
                                                  was_failed)
                emit = Emit(dst=jnp.where(apply, emit.dst, -1),
                            payload=emit.payload,
                            valid=emit.valid & apply,
                            type=emit.type)
                if not counts:
                    return merged, emit
                # a row that did not run takes its slots all the same, as
                # a row without an emission width does
                return merged, emit, jnp.where(
                    apply, counts[0],
                    jnp.asarray([self.slots, 0, 0], jnp.int32))

            return per_actor

        if extent is None:
            per_actor_inbox = ((*place(n), *mail) if self.slots > 0
                               else (d.sum, d.max, d.count))
            with jax.named_scope("akka.behavior"):
                new_state, *emits = jax.vmap(per_actor_of(self._branches))(
                    state, behavior_id, alive, ids, *per_actor_inbox)
        else:
            r = extent

            def slots_of(width):
                with jax.named_scope("akka.deliver"):
                    return place(width)

            def every_row(_):
                # a live row at or past R takes slots this step
                branches = [self._wrap(b, ".all") for b in self.behaviors]
                with jax.named_scope("akka.behavior"):
                    new, *out = jax.vmap(per_actor_of(branches))(
                        state, behavior_id, alive, ids,
                        *slots_of(n), *mail)
                return new, [jax.tree.map(lambda c: c[:r], out),
                             jax.tree.map(lambda c: c[r:], out)]

            def split(_):
                written = set()
                tail = [self._untouched(b) if b.inbox == "slots"
                        else self._wrap(b, written=written)
                        for b in self.behaviors]
                types, payload = slots_of(r)
                no_slots = (jnp.zeros((n - r,) + types.shape[1:], types.dtype),
                            jnp.zeros((n - r,) + payload.shape[1:],
                                      payload.dtype))
                put = jax.lax.dynamic_update_slice_in_dim
                with jax.named_scope("akka.behavior"):
                    head_state, *head = jax.vmap(per_actor_of(self._branches))(
                        {k: v[:r] for k, v in state.items()}, behavior_id[:r],
                        alive[:r], ids[:r], types, payload,
                        *(c[:r] for c in mail))
                    tail_state, *rest = jax.vmap(per_actor_of(tail))(
                        {k: v[r:] for k, v in state.items()}, behavior_id[r:],
                        alive[r:], ids[r:], *no_slots,
                        *(c[r:] for c in mail))
                    new = {k: put(v, head_state[k], 0, axis=0)
                           for k, v in state.items()}
                    for k in new:  # the state's order, not the set's
                        if k in written:
                            new[k] = put(new[k], tail_state[k], r, axis=0)
                return new, [head, rest]

            # outside the layers' scopes: each branch holds the enqueue's
            # turns (`akka.deliver`) and a switch (`akka.behavior`)
            new_state, halves = jax.lax.cond(beyond, every_row, split, None)
        with jax.named_scope("akka.behavior"):
            if extent is not None:
                emits = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                                     *halves)
            emits = tuple(emits) if self.emit_width else emits[0]
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

    # --------------------------------------------------------------- emit
    def pack_emits(self, emit: Emit):
        """The emitting path's emission rows (`SlotEmits.emit`, [N, W]) in
        the inbox's layout: rows [0, emit_rows) packed to `emit_width`
        rows each, the others to `out_degree`, each row's valid emissions
        first and in order. Returns (Emit of R * emit_width + (N - R) *
        out_degree rows, [N] int32 sent a row, dropped): what a row past
        `emit_rows` sent beyond `out_degree` is lost, and counted."""
        r = self.emit_rows
        head, sent_head = _pack(jax.tree.map(lambda c: c[:r], emit),
                                self.emit_width)
        tail, sent_tail = _pack(jax.tree.map(lambda c: c[r:], emit),
                                self.out_degree)
        rows = Emit(*(jnp.concatenate([a.reshape((-1,) + a.shape[2:]),
                                       b.reshape((-1,) + b.shape[2:])])
                      for a, b in zip(head, tail)))
        lost = jnp.sum(jnp.maximum(sent_tail - self.out_degree, 0))
        return rows, jnp.concatenate([sent_head, sent_tail]), lost

    @staticmethod
    def emit_lanes(old, counts, new_state, sent):
        """The five emission counters (`core.EMIT_STATS`) after a step, from
        their values before it (`old(i)`, lane i of the five), the step's
        `SlotEmits.counts`, the new state (its `stash_size`) and what each
        row sent (`pack_emits`): `stashed`, `stash_overflow` and
        `retained_by_budget` summed, the two high waters the larger. Both
        drivers keep them: `spill_stats` on one chip, a shard's row of
        `mesh_stats` on the mesh."""
        held = new_state["stash_size"] if "stash_size" in new_state \
            else jnp.zeros((1,))
        return [old(0) + counts[0],
                jnp.maximum(old(1), jnp.max(held).astype(jnp.int32)),
                old(2) + counts[1],
                old(3) + counts[2],
                jnp.maximum(old(4), jnp.max(sent))]

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
        already computes it). Where a behavior declares an emission width,
        `emits` is a `SlotEmits` (packed by `pack_emits`) and `spill` is
        what retention left after the folds (block `akka.emit.retain`)."""
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
        extent, beyond = self.beyond_extent(behavior_id, alive,
                                            slots_kind_row,
                                            state.get("_failed"))
        delivered = self.deliver(inbox_dst, inbox_type, inbox_payload,
                                 inbox_valid, topo_arrays, dst_offset,
                                 slots_kind_row, suspended, extent)
        new_state, new_behavior_id, alive, emits, sup_delta = self.update(
            state, behavior_id, alive, delivered, step_count, id_base, tables,
            extent, beyond)
        d, _, retain = (delivered if self.slots > 0
                        else (delivered, None, None))
        spill = None
        if self.emit_width:
            emit, counts = emits
            # what the folds left unhandled goes to the spill region with
            # the mailboxes' overflow, ahead of the next step's mail
            with jax.named_scope("akka.emit"), \
                    jax.named_scope("akka.emit.retain"):
                spill, dropped, retained = retain(counts[:, 0])
                if dst_offset is not None:
                    spill = (jnp.where(spill[3], spill[0] + dst_offset, -1),
                             ) + spill[1:]
                emits = SlotEmits(emit, jnp.stack(
                    [jnp.sum(counts[:, 1]), jnp.sum(counts[:, 2]),
                     retained]).astype(jnp.int32))
        elif self.slots > 0 and self.spill_cap > 0:
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

    # ------------------------------------------------------- slots extent
    EXTENT_ROWS = 1024  # whole (8, 128) tiles

    def note_rows(self, behavior_ids, rows) -> None:
        """Host side: the local `rows` now run `behavior_ids` (one id, or
        one a row). The highest row given a slots behavior raises the
        slots extent of the steps traced after this."""
        rows = np.asarray(rows, np.int64).reshape(-1)
        kinds = np.asarray([b.inbox == "slots" for b in self.behaviors])
        slots_rows = rows[kinds[np.broadcast_to(behavior_ids, rows.shape)]]
        if slots_rows.size:
            self._slots_top = max(self._slots_top,
                                  int(slots_rows.max()) + 1)

    def beyond_extent(self, behavior_id, alive, slots_kind_row=None,
                      failed=None):
        """(extent, beyond) for this step's slots delivery and update: the
        slots extent as a static int, and whether a live row at or past it
        takes slots, however it came to (a device `_become`, a restore, a
        behavior id written directly); (None, None) where there is no
        extent. A row with its `failed` flag up is not live: its receive
        does not run, and nothing reads its slots. Records the extent as
        the one compiled."""
        if self.slots == 0:
            return None, None
        extent = self.slots_extent
        self.compiled_extent = extent
        if extent >= self.n_local:
            return None, None
        kind = (self._slots_kind[behavior_id[extent:]]
                if slots_kind_row is None else slots_kind_row[extent:])
        with jax.named_scope("akka.deliver"), \
                jax.named_scope("akka.deliver.place"):
            live = alive[extent:]
            if failed is not None:
                live = live & ~failed[extent:]
            return extent, jnp.any(live & kind)

    @property
    def slots_extent(self) -> int:
        """Every local row the host gave a slots behavior stands below it:
        one past the highest, rounded up to whole tiles, at most n_local;
        n_local where none was given (or the system delivers no slots)."""
        if self.slots == 0 or self._slots_top == 0:
            return self.n_local
        tile = self.EXTENT_ROWS
        return min(self.n_local, -(-self._slots_top // tile) * tile)


def _widen(emit: Emit, width: int) -> Emit:
    """An emission block padded with empty rows to `width`."""
    more = width - emit.dst.shape[0]
    if more <= 0:
        return emit
    pad = Emit.none(more, emit.payload.shape[-1], emit.payload.dtype)
    return Emit(*(jnp.concatenate([a, b]) for a, b in zip(emit, pad)))


def _pack(emit: Emit, width: int):
    """Each row's valid emissions ([n, W] blocks) moved to its first
    `width` rows, in order: row k of a block takes the valid emission with
    k valid ones before it. Selects and sums over W; the running count is
    W adds, no cumsum (whose TPU form carries no scope). Returns the packed
    Emit ([n, width]) and the valid emissions a row ([n] int32)."""
    valid = emit.valid
    n, w = valid.shape
    before, run = [], jnp.zeros((n,), jnp.int32)
    for j in range(w):
        before.append(run)
        run = run + valid[:, j].astype(jnp.int32)
    hit = valid[:, :, None] & (jnp.stack(before, axis=1)[:, :, None]
                               == jnp.arange(width, dtype=jnp.int32))

    def take(col):
        h = hit.reshape(hit.shape + (1,) * (col.ndim - 2))
        return jnp.sum(jnp.where(h, col[:, :, None], jnp.zeros((), col.dtype)),
                       axis=1)

    got = jnp.any(hit, axis=1)
    packed = Emit(dst=jnp.where(got, take(emit.dst), -1),
                  payload=take(emit.payload), valid=got, type=take(emit.type))
    return packed, run


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
