"""Batched behaviors: vmapped per-actor update functions.

This is the TPU-native replacement for the reference's receive loop
(dispatch/Mailbox.scala:260-277 processMailbox + actor/ActorCell.scala:539-555
invoke): instead of dequeue-and-call per actor on a thread pool, every live
actor's update runs as ONE vmapped, jitted function per step, selected by
behavior id via lax.switch (the tensorized analogue of the typed interpreter's
tag switch, typed/Behavior.scala:244-278).

A BatchedBehavior declares:
- a fixed per-actor state schema (SoA columns),
- `receive_batch(state_row, inbox, ctx) -> (new_state_row, Emit)` written in
  scalar JAX (it will be vmapped), where `inbox` carries the segment-reduced
  payload sum/max and message count for this actor this step.

Message delivery is commutative-reduction (segment_sum over recipient ids) —
the GNN-style message passing of the BASELINE north star. Per-sender FIFO
ordering within a step is preserved by construction (each actor emits at most
`out_degree` messages per step; reductions are order-insensitive).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Inbox(NamedTuple):
    """What a single actor sees from one step's delivery (all per-actor slices,
    scalar/vector shaped — the runtime vmaps over actors)."""

    sum: jax.Array    # [P] segment-sum of payloads addressed to this actor
    max: jax.Array    # [P] segment-max (useful for latched signals / LWW)
    count: jax.Array  # [] int32 number of messages delivered


class Mailbox(NamedTuple):
    """One actor's per-message mailbox for one step (slots mode): up to S
    discrete messages in arrival order — the tensorized Envelope queue of
    the reference (dispatch/Mailbox.scala:260-277). Slot i is older than
    slot i+1; per-sender FIFO is guaranteed by stable (recipient, seq)
    delivery (ops/segment.py deliver_slots)."""

    types: jax.Array    # [S] int32 message-type tags
    payload: jax.Array  # [S, P]
    valid: jax.Array    # [S] bool
    count: jax.Array    # [] int32 messages addressed this step (can be > S)
    sum: jax.Array      # [P] exact sum over ALL addressed messages
    max: jax.Array      # [P] exact max over ALL messages (zeros unless the
                        #     system was built with need_max)
    budget: Any = None  # the behavior's `emit_width` (a static int), set by
                        #     the runtime for a behavior that declares one

    def fold(self, init_carry, fn):
        """Process slots in FIFO order: fn(carry, mtype, payload) -> carry,
        applied only to valid slots (lax.scan over S — the processMailbox
        dequeue loop as a scan). Returns the final carry."""
        def body(carry, slot):
            t, pl, v = slot
            new = fn(carry, t, pl)
            return jax.tree.map(
                lambda a, b: jnp.where(_bshape(v, a), a, b), new, carry), None
        carry, _ = jax.lax.scan(body, init_carry,
                                (self.types, self.payload, self.valid))
        return carry

    def fold_emit(self, init_carry, fn, stash: "StashBuffer" = None,
                  is_open=None):
        """The fold of an actor that answers: one message at a time, each
        handled message sending at most one of its own, under the step's
        emission budget (`budget`, the behavior's `emit_width`).

        `fn(carry, mtype, payload)` returns `(carry, emit)`, `emit` an
        `Emit` of width 1 (or none), and with a `stash` also a bool: stash
        this message instead of handling it (Akka typed's
        `StashBuffer.stash`; a stashed message leaves the carry as it was
        and sends nothing). `is_open(carry)` says whether the stash is
        replayed: while it holds and the stash is not empty, the stash's
        oldest message is handled before any mailbox message
        (`unstashAll`: the whole stash, in order, ahead of newer mail).
        A message taken from the stash is handled, never stashed again:
        `is_open` holds exactly where `fn` would not stash.

        The budget: messages are handled in order, the stash's first, and
        their emissions counted; the first message whose emission would
        pass `budget` ends the row's step. It stays where it was (in the
        stash, or in the mailbox with every later slot: the runtime
        retains those ahead of the next step's mail). At most `budget`
        stash messages are replayed a step.

        Returns (carry, stash, Folded): the stash as the step leaves it
        (replayed messages gone, stashed ones appended, oldest first) and
        what the runtime reads: one emission row a turn, in processing
        order, and the counts."""
        width = self.budget
        if not isinstance(width, int) or width < 1:
            raise ValueError("fold_emit needs the behavior's emission budget: "
                             "declare emit_width on a slots behavior")
        n_slots = self.types.shape[0]
        n_mail = jnp.sum(self.valid.astype(jnp.int32))  # a prefix
        turns = n_slots + (width if stash is not None else 0)
        if stash is not None:
            cap = stash.types.shape[0]
            held = stash.size
            # the first `width` entries of the queue the step sees: the
            # stash as it stood (its ring read from `head` on, one pass),
            # then what the step stashes
            at = (jnp.arange(cap) - stash.head) % cap
            hit = (at[:, None] == jnp.arange(width)) & (at < held)[:, None]
            hot = (jnp.sum(jnp.where(hit, stash.types[:, None], 0), axis=0),
                   jnp.sum(jnp.where(hit[:, :, None], stash.payload[:, None],
                                     jnp.zeros((), stash.payload.dtype)),
                           axis=0))
        else:
            hot = (jnp.zeros((0,), jnp.int32),
                   jnp.zeros((0,) + self.payload.shape[1:],
                             self.payload.dtype))
            held = jnp.asarray(0, jnp.int32)
        zero = jnp.asarray(0, jnp.int32)

        def turn(c, _):
            carry, used, mi, popped, added, lost, stopped, hot_t, hot_p = c
            from_mail_slot = (_pick(self.types, mi), _pick(self.payload, mi))
            if stash is not None:
                open_ = (jnp.asarray(is_open(carry), jnp.bool_)
                         if is_open is not None else jnp.asarray(True))
                replay = ~stopped & open_ & (held + added - popped > 0)
                can_pop = popped < width
                from_stash = (_pick(hot_t, popped), _pick(hot_p, popped))
                mtype, pl = (jnp.where(replay, a, b) for a, b in
                             zip(from_stash, from_mail_slot))
                new, em, stash_it = fn(carry, mtype, pl)
            else:
                replay = can_pop = jnp.asarray(False)
                mtype, pl = from_mail_slot
                new, em = fn(carry, mtype, pl)
                stash_it = False
            em = em.with_type()
            if em.dst.shape[0] != 1:
                raise ValueError("fold_emit: a handled message sends one "
                                 "message or none (an Emit of width 1)")
            mail = ~stopped & ~replay & (mi < n_mail)
            shelve = mail & jnp.asarray(stash_it, jnp.bool_)
            e = em.valid[0].astype(jnp.int32)
            fits = used + e <= width
            popping = replay & can_pop & fits
            handled = popping | (mail & ~shelve & fits)
            stopped = stopped | (replay & ~(can_pop & fits)) \
                | (mail & ~shelve & ~fits)
            carry = jax.tree.map(
                lambda a, b: jnp.where(_bshape(handled, a), a, b), new, carry)
            used = used + jnp.where(handled, e, 0)
            mi = mi + (mail & (shelve | fits)).astype(jnp.int32)
            if stash is not None:
                room = held + added - popped < cap
                keep = shelve & room
                # its place in the queue the step sees, where that is hot
                into = keep & (jnp.arange(width) == held + added)
                hot_t = jnp.where(into, mtype, hot_t)
                hot_p = jnp.where(into[:, None], pl, hot_p)
                added = added + keep.astype(jnp.int32)
                lost = lost + (shelve & ~room).astype(jnp.int32)
                popped = popped + popping.astype(jnp.int32)
            else:
                keep = shelve
            sent = em.valid[0] & handled
            # the emission, then the message stashed and its place among
            # the step's stashed ones
            row = (jnp.where(sent, em.dst[0], -1), em.type[0], em.payload[0],
                   sent, keep, added - keep.astype(jnp.int32), mtype, pl)
            return (carry, used, mi, popped, added, lost, stopped, hot_t,
                    hot_p), row

        c0 = (init_carry, zero, zero, zero, zero, zero, jnp.asarray(False))
        (carry, _, taken, popped, added, lost, _, _, _), rows = jax.lax.scan(
            turn, c0 + hot, None, length=turns)
        dst, etype, epayload, evalid, kept, order, stype, spayload = rows
        folded = Folded(emit=Emit(dst=dst, payload=epayload, valid=evalid,
                                  type=etype),
                        taken=taken, stashed=added, lost=lost)
        if stash is None:
            return carry, None, folded
        # the stash the step leaves: the replayed ones gone from the front
        # of its ring, what it stashed written behind what it held, in the
        # order stashed (one pass over the ring)
        ring = (stash.head + held + order) % cap
        put = kept[None, :] & (ring[None, :] == jnp.arange(cap)[:, None])
        anew = jnp.any(put, axis=1)
        types = jnp.where(anew, jnp.sum(jnp.where(put, stype[None, :], 0),
                                        axis=1), stash.types)
        payload = jnp.where(anew[:, None], jnp.sum(
            jnp.where(put[:, :, None], spayload[None],
                      jnp.zeros((), spayload.dtype)), axis=1), stash.payload)
        head = (stash.head + popped) % cap
        size = held + added - popped
        return carry, StashBuffer(types, payload, head, size), folded

    def reduce(self) -> "Inbox":
        """Commutative view so reduce-kind behaviors run unmodified inside a
        slots-mode system. Uses the delivery's EXACT full-inbox aggregation
        (computed over all addressed messages, not just the S slot-resident
        ones) — slot overflow never corrupts reduce-behavior state."""
        return Inbox(sum=self.sum, max=self.max, count=self.count)


def _bshape(cond, like):
    """Broadcast a scalar bool against an arbitrary-rank carry leaf."""
    return jnp.reshape(cond, (1,) * like.ndim) if like.ndim else cond


def _pick(rows, i):
    """Row `i` (a traced index) of `rows`, as a select over the rows: no
    gather in the vmapped fold. A row past the end reads 0."""
    hit = jnp.arange(rows.shape[0]) == i
    return jnp.sum(jnp.where(hit.reshape((-1,) + (1,) * (rows.ndim - 1)),
                             rows, jnp.zeros((), rows.dtype)), axis=0)


class StashBuffer(NamedTuple):
    """One row's stash (Akka typed's `StashBuffer`, bounded): up to C
    `(type, payload)` messages in state columns, a ring: the oldest at
    `head`, the q-th at `(head + q) mod C`, so that a replay moves no
    message and a step writes the ring once. `spec` gives the columns a
    behavior adds to its `state_spec`, `of` reads them from a state row,
    `columns` writes them back, `messages` lists them, oldest first."""

    types: jax.Array    # [C] int32
    payload: jax.Array  # [C, P]
    head: jax.Array     # [] int32 where the oldest stands
    size: jax.Array     # [] int32 messages held

    COLUMNS = ("stash_type", "stash_payload", "stash_head", "stash_size")

    @staticmethod
    def spec(capacity: int, payload_width: int, dtype=jnp.float32) -> dict:
        return {"stash_type": ((capacity,), jnp.int32),
                "stash_payload": ((capacity, payload_width), dtype),
                "stash_head": ((), jnp.int32),
                "stash_size": ((), jnp.int32)}

    @staticmethod
    def of(state) -> "StashBuffer":
        return StashBuffer(*(state[c] for c in StashBuffer.COLUMNS))

    def columns(self) -> dict:
        return dict(zip(StashBuffer.COLUMNS, self))


class Folded(NamedTuple):
    """What `Mailbox.fold_emit` hands the runtime for one row."""

    emit: "Emit"         # [S (+ budget)] one row a turn, in processing
                         #     order; `valid` where a message was sent
    taken: jax.Array     # [] mailbox slots handled or stashed; the slots
                         #     past them are retained for the next step
    stashed: jax.Array   # [] messages put in the stash this step
    lost: jax.Array      # [] messages the full stash could not take


class Emit(NamedTuple):
    """Up to K outgoing messages from one actor in one step."""

    dst: jax.Array      # [K] int32 recipient ids (global); -1 = none
    payload: jax.Array  # [K, P]
    valid: jax.Array    # [K] bool
    type: Any = None    # [K] int32 message-type tags (None -> all zeros)

    @staticmethod
    def none(out_degree: int, payload_width: int, dtype=jnp.float32) -> "Emit":
        return Emit(
            dst=jnp.full((out_degree,), -1, dtype=jnp.int32),
            payload=jnp.zeros((out_degree, payload_width), dtype=dtype),
            valid=jnp.zeros((out_degree,), dtype=jnp.bool_),
            type=jnp.zeros((out_degree,), dtype=jnp.int32),
        )

    @staticmethod
    def single(dst, payload, out_degree: int, payload_width: int,
               when=True, dtype=jnp.float32, mtype=0) -> "Emit":
        """One message in slot 0, rest empty. `when` may be a traced bool."""
        e = Emit.none(out_degree, payload_width, dtype)
        pl = jnp.asarray(payload, dtype=dtype).reshape(-1)
        pl = jnp.pad(pl, (0, payload_width - pl.shape[0]))
        cond = jnp.asarray(when, dtype=jnp.bool_)
        return Emit(
            dst=e.dst.at[0].set(jnp.where(cond, jnp.asarray(dst, jnp.int32), -1)),
            payload=e.payload.at[0].set(pl),
            valid=e.valid.at[0].set(cond),
            type=e.type.at[0].set(jnp.asarray(mtype, jnp.int32)),
        )

    def with_type(self) -> "Emit":
        """Normalize: a None type column becomes zeros (trace-time check)."""
        if self.type is None:
            return self._replace(type=jnp.zeros_like(self.dst))
        return self


class Ctx(NamedTuple):
    """Per-actor step context."""

    actor_id: jax.Array  # [] int32 — this actor's global id
    step: jax.Array      # [] int32 — global step counter
    n_actors: jax.Array  # [] int32 — capacity of the actor space
    tables: Any = ()     # runtime lookup tables (dict of small arrays,
                         # NOT vmapped — e.g. the device-sharding
                         # logical-shard -> row-base placement table)


@dataclass
class BatchedBehavior:
    """The batched analogue of Behavior[T].

    Two inbox kinds (`inbox` field):
    - "reduce" (default): `receive(state_row, inbox: Inbox, ctx)` sees the
      commutative (sum, max, count) aggregation — the fast path for
      GNN-shaped/commutative actors (one segment reduction, no per-message
      state on device).
    - "slots": `receive(state_row, mailbox: Mailbox, ctx)` sees up to S
      discrete (type, payload) messages in per-sender-FIFO arrival order —
      full Akka mailbox semantics (dispatch/Mailbox.scala:260-277) for
      non-commutative behaviors (order-dependent state machines, bank
      accounts, FSMs).

    A slots-mode system runs both kinds (reduce behaviors get
    `mailbox.reduce()`); a reduce-mode system rejects slots behaviors.
    Runs only for actors whose `count > 0` unless `always_on`.

    `supervisor` (batched/supervision.py LaneSupervisor) compiles a
    fault-handling directive into the step: lanes raising `_failed` are
    resumed/restarted/stopped/escalated in-graph, no host round-trip.
    `nonfinite_guard` (opt-in) marks a lane `_failed` when its new state
    row contains NaN/Inf — the pre-failure state is retained, exactly like
    a failing receive, instead of the NaN silently poisoning every
    subsequent reduce.

    `emit_width` (slots behaviors only) makes the actor one that answers:
    its `receive` folds with `Mailbox.fold_emit` and returns `(columns,
    Folded)`, a message sent a handled message, at most `emit_width` a
    step (the row's emission budget; `Mailbox.budget`). The system then
    gives the rows below its `emit_rows` that many emission rows each, and
    retains what the budget left unhandled ahead of the next step's mail.
    0: the behavior returns an `Emit` of the system's out-degree.
    """

    name: str
    state_spec: Dict[str, Tuple[Tuple[int, ...], Any]]  # col -> (shape, dtype)
    receive: Callable[..., Tuple[Dict[str, jax.Array], Emit]]
    always_on: bool = False
    inbox: str = "reduce"  # "reduce" | "slots"
    supervisor: Any = None  # Optional[supervision.LaneSupervisor]
    nonfinite_guard: bool = False
    emit_width: int = 0

    def init_state(self, n: int) -> Dict[str, jax.Array]:
        return {k: jnp.zeros((n,) + tuple(shape), dtype=dtype)
                for k, (shape, dtype) in self.state_spec.items()}


def behavior(name: str, state_spec: Dict[str, Tuple[Tuple[int, ...], Any]],
             always_on: bool = False, inbox: str = "reduce",
             supervisor: Any = None, nonfinite_guard: bool = False,
             emit_width: int = 0):
    """Decorator: @behavior("counter", {"count": ((), jnp.int32)})"""

    def deco(fn) -> BatchedBehavior:
        return BatchedBehavior(name=name, state_spec=state_spec, receive=fn,
                               always_on=always_on, inbox=inbox,
                               supervisor=supervisor,
                               nonfinite_guard=nonfinite_guard,
                               emit_width=emit_width)

    return deco
