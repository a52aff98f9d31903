"""Routing on the device tier: a pool as a row, routed inside the step.

A pool router is an actor: a row of the actor table with a ref (its row id),
whose state columns hold the pool's one counter `next` and the count `routed`
(`ROUTER_SPEC`). Senders hold the ref and tell it like any actor; they know no
routee. `BatchedRouter` describes the pool, and the step's route stage
(`StepCore.route`, scope `akka.route`) executes it ahead of delivery: a
message addressed to the router's row is readdressed to a routee chosen from
the message's sequence number, so the router's mailbox is never on the path
(akka.routing.RoutedActorCell.sendMessage; RoundRobinRoutingLogic's one
AtomicLong a router, `next.getAndIncrement % size`; SURVEY.md §2.11). See
docs/ROUTING.md.

`consistent_hash_dst` needs no pool state and stays a pure sender-side
function.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..batched.behavior import BatchedBehavior, Emit

ROUTER_SPEC = {"next": ((), jnp.int32), "routed": ((), jnp.int32)}


def _fnv1a(x: jax.Array) -> jax.Array:
    """Vectorized 32-bit FNV-1a-style mix of int32 keys (device-side stand-in
    for the reference's MurmurHash, routing/MurmurHash.scala)."""
    x = x.astype(jnp.uint32)
    h = jnp.uint32(2166136261)
    for shift in (0, 8, 16, 24):
        byte = (x >> shift) & jnp.uint32(0xFF)
        h = (h ^ byte) * jnp.uint32(16777619)
    return h


def consistent_hash_dst(keys: jax.Array, routee_base: int, n_routees: int) -> jax.Array:
    """Map int32 hash keys to stable routee destinations."""
    return routee_base + (_fnv1a(keys) % jnp.uint32(n_routees)).astype(jnp.int32)


def broadcast_dst(n_routees: int, routee_base: int) -> jax.Array:
    """All routees (use with out_degree = n_routees emissions)."""
    return routee_base + jnp.arange(n_routees, dtype=jnp.int32)


class BatchedRouter:
    """A pool behind one ref: `n_routees` routees in the rows
    [routee_base, routee_base + n_routees), the router itself in `row`.

    The route stage gives the step's messages addressed to `row` the
    sequence numbers `next + k`, k = 0, 1, ... in inbox row order (retained
    spill, then each actor's emission slots by actor row, then the host's
    tells), and sends number s to routee `index_of(s)`:

    - "round-robin": s mod n_routees; after every step the routees' loads
      differ by at most one, whichever senders told.
    - "random": a hash of s (`_fnv1a`) mod n_routees.

    `behavior` is what the router's row is spawned with: the columns of
    `ROUTER_SPEC` and a receive that is never run, since no message stays
    addressed to the row.
    """

    LOGICS = ("round-robin", "random")

    def __init__(self, logic: str, row: int, routee_base: int,
                 n_routees: int, out_degree: int = 1, payload_width: int = 4):
        if logic not in self.LOGICS:
            raise ValueError(f"unknown routing logic {logic!r}; "
                             f"one of {self.LOGICS}")
        if n_routees <= 0:
            raise ValueError("n_routees must be > 0")
        if routee_base <= row < routee_base + n_routees:
            raise ValueError(f"router row {row} lies among its own routees")
        self.logic = logic
        self.row = int(row)
        self.routee_base = int(routee_base)
        self.n_routees = int(n_routees)
        self.behavior = BatchedBehavior(
            name="router", state_spec=dict(ROUTER_SPEC),
            receive=lambda state, inbox, ctx: (
                {}, Emit.none(out_degree, payload_width)))

    def index_of(self, seq: jax.Array) -> jax.Array:
        """Routee index in [0, n_routees) of each sequence number (uint32)."""
        if self.logic == "random":
            seq = _fnv1a(seq)
        return (seq % jnp.uint32(self.n_routees)).astype(jnp.int32)
