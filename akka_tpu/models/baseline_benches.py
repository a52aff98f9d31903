"""The BASELINE.json bench topologies as batched-behavior 'models'.

These mirror akka-bench-jmh's harnesses (SURVEY.md §6):
- ring:      1M-actor ring, every actor holds one token and forwards to the
             next each step (the ForkJoinActorBenchmark ping-pong generalized)
- fan_in:    1M leaves -> 1k collectors that keep count, sums and max (the
             segment-reduction hot path, `Inbox.max` included)
- ping_pong: 2-actor TellOnlyBenchmark equivalent
- router_pool: producers tell ONE router ref; the step's route stage
             spreads their tells round-robin over 100k routees
- bank:      tellers tell bank-account entities Deposit / Withdraw-if-
             sufficient; every account folds its mailbox in arrival order
             (slots delivery, overflow spilled and redelivered FIFO)
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..batched import BatchedSystem, Ctx, Emit, Inbox, Mailbox, behavior
from ..batched.sharded import ShardedBatchedSystem

PAYLOAD_W = 4


@behavior("ring", {"received": ((), jnp.int32)})
def ring_behavior(state, inbox, ctx):
    nxt = (ctx.actor_id + 1) % ctx.n_actors
    return ({"received": state["received"] + inbox.count},
            Emit.single(nxt, inbox.sum, 1, PAYLOAD_W, when=inbox.count > 0))


# --- config 3: the fan-in aggregator (telemetry / metering back ends: one
# actor per device, one aggregator per group, every device reporting every
# tick; the shape of the Akka guide's IoT example, Device -> DeviceGroup).
# A leaf holds the ref of its collector and its readings in its own state,
# given at spawn; a collector keeps count, sums and max of what it is told.
LEAF_SPEC = {"collector": ((), jnp.int32), "reading_a": ((), jnp.int32),
             "reading_b": ((), jnp.int32), "alarm_level": ((), jnp.int32),
             "phase": ((), jnp.int32)}
COLLECTOR_SPEC = {k: ((), jnp.int32) for k in (
    "msgs", "sum0", "sum1", "sum2", "sum3", "alarms", "peak_total",
    "last_max")}


def make_fan_in_leaf(reading_levels: int = 8, alarm_period: int = 16):
    """Leaf behavior: at step t it tells the collector it holds
    `[1, a, (b + t) mod reading_levels, alarm]`, where `alarm` is its
    `alarm_level` on the steps with `(t + phase) mod alarm_period == 0` and
    0 otherwise (a leaf with `alarm_level` 0 never alarms). The destination
    is the `collector` state column, never arithmetic on the actor id."""

    @behavior("leaf", LEAF_SPEC, always_on=True)
    def fan_in_leaf(state, inbox, ctx):
        t = ctx.step
        fires = (state["alarm_level"] > 0) & \
            ((t + state["phase"]) % alarm_period == 0)
        reading = jnp.stack([
            jnp.ones((), jnp.int32), state["reading_a"],
            (state["reading_b"] + t) % reading_levels,
            jnp.where(fires, state["alarm_level"], 0)])
        return {}, Emit.single(state["collector"], reading, 1, PAYLOAD_W)

    return fan_in_leaf


@behavior("collector", COLLECTOR_SPEC)
def fan_in_collector(state, inbox, ctx):
    """Count, one running total per payload column, and the max of column 3
    (the alarm level), all int32: a step's sums are exact f32 integers, and
    int32 wraps modulo 2^32 where an f32 total would stop counting at 2^24."""
    top = inbox.max[3].astype(jnp.int32)
    new = {f"sum{j}": state[f"sum{j}"] + inbox.sum[j].astype(jnp.int32)
           for j in range(PAYLOAD_W)}
    new.update(msgs=state["msgs"] + inbox.count,
               alarms=state["alarms"] + (top > 0).astype(jnp.int32),
               peak_total=state["peak_total"] + top, last_max=top)
    return new, Emit.none(1, PAYLOAD_W)


def fan_in_leaves(n_leaves: int, n_collectors: int, seed: int = 0,
                  reading_levels: int = 8, alarm_one_in: int = 64,
                  alarm_period: int = 16) -> dict:
    """A deployment's leaves from a seed, as the columns of `LEAF_SPEC`:
    the collector each is wired to (uniform over the collectors), two
    readings below `reading_levels`, and for one leaf in `alarm_one_in` an
    alarm level in [1, reading_levels) with a phase below `alarm_period`."""
    rng = np.random.default_rng([int(seed), n_leaves, n_collectors])
    capable = rng.integers(0, alarm_one_in, n_leaves) == 0
    return {
        "collector": rng.integers(0, n_collectors, n_leaves),
        "reading_a": rng.integers(0, reading_levels, n_leaves),
        "reading_b": rng.integers(0, reading_levels, n_leaves),
        "alarm_level": np.where(
            capable, rng.integers(1, reading_levels, n_leaves), 0),
        "phase": rng.integers(0, alarm_period, n_leaves)}


def build_ring(n: int = 1 << 20, sharded: bool = False, n_devices=None,
               static: bool = True, delivery: str = "auto"):
    if sharded:
        sys = ShardedBatchedSystem(capacity=n, behaviors=[ring_behavior],
                                   n_devices=n_devices, payload_width=PAYLOAD_W,
                                   host_inbox_per_shard=8, delivery=delivery)
    else:
        topo = None
        if static:
            # the ring's wiring is fixed -> compile delivery to a gather
            from akka_tpu.ops.segment import StaticTopology
            dst_table = ((np.arange(n, dtype=np.int64) + 1) % n)[:, None]
            topo = StaticTopology.from_dst_table(dst_table)
        sys = BatchedSystem(capacity=n, behaviors=[ring_behavior],
                            payload_width=PAYLOAD_W, host_inbox=8,
                            topology=topo)
    sys.spawn_block(ring_behavior, n)
    return sys


def seed_ring_full(sys) -> None:
    """Every actor holds one token (uniform 1-msg mailbox per BASELINE config)."""
    n = sys.capacity
    dst = jnp.arange(n, dtype=jnp.int32)
    payload = jnp.zeros((n, PAYLOAD_W), dtype=jnp.float32).at[:, 0].set(1.0)
    if hasattr(sys, "seed_inbox"):
        sys.seed_inbox(dst, payload)
    else:  # sharded: place into each shard's exchange region
        seed_sharded_ring(sys)


def seed_sharded_ring(sys: ShardedBatchedSystem) -> None:
    """Seed one token per actor directly into each shard's self-chunk of the
    exchange buffer (slot layout: shard s's inbox[s*pair_cap + r])."""
    import jax
    n = sys.capacity
    # inbox is globally [n_shards * m_local]; shard s's block starts at s*m_local;
    # its self-chunk (from shard s) is at offset s*pair_cap within the block
    idxs, dsts = [], []
    for s in range(sys.n_shards):
        base = s * sys.m_local + sys.spill_cap + s * sys.pair_cap
        for r in range(min(sys.local_n, sys.pair_cap)):
            idxs.append(base + r)
            dsts.append(s * sys.local_n + r)
    idx = jnp.asarray(idxs)
    sys.inbox_dst = sys.inbox_dst.at[idx].set(jnp.asarray(dsts, jnp.int32))
    sys.inbox_payload = sys.inbox_payload.at[idx, 0].set(1.0)
    sys.inbox_valid = sys.inbox_valid.at[idx].set(True)


def build_fan_in(n_leaves: int = 1 << 20, n_collectors: int = 1000,
                 static: bool = True, leaves=None, seed: int = 0,
                 reading_levels: int = 8, alarm_period: int = 16,
                 delivery: str = "auto", delivery_backend=None):
    """Config 3: collectors in rows [0, n_collectors), leaves in the next
    n_leaves rows. `leaves` gives each leaf's state at spawn (the columns of
    `LEAF_SPEC`: the collector it tells, its readings, its alarm); without
    it `fan_in_leaves(seed=seed)` draws one. With `static` the wiring handed
    out at spawn is also compiled into a `StaticTopology`; without it the
    runtime does not know the graph and delivery is dynamic, `Inbox.max`
    (`need_max`) included."""
    n = n_leaves + n_collectors
    if leaves is None:
        leaves = fan_in_leaves(n_leaves, n_collectors, seed, reading_levels,
                               alarm_period=alarm_period)
    leaves = {k: np.asarray(leaves[k], np.int32) for k in LEAF_SPEC}
    topo = None
    if static:
        from akka_tpu.ops.segment import StaticTopology
        dst_table = np.concatenate([np.full(n_collectors, -1, np.int64),
                                    leaves["collector"]])[:, None]
        topo = StaticTopology.from_dst_table(dst_table)
    leaf = make_fan_in_leaf(reading_levels, alarm_period)
    sys = BatchedSystem(capacity=n, behaviors=[fan_in_collector, leaf],
                        payload_width=PAYLOAD_W, host_inbox=8, topology=topo,
                        need_max=True, delivery=delivery,
                        delivery_backend=delivery_backend)
    sys.spawn_block(fan_in_collector, n_collectors)
    sys.spawn_block(leaf, n_leaves, init_state=leaves)
    return sys


# --- config 4: the router pool (worker pools behind one ref: job dispatch,
# request fan-out to stateless workers). A producer holds the ROUTER's ref in
# its state, given at spawn, and tells it; it knows no routee. The pool's
# logic runs inside the step (routing/batched.py BatchedRouter, StepCore's
# route stage): one shared counter a router, as akka.routing.RoundRobinPool.
PRODUCER_SPEC = {k: ((), jnp.int32) for k in (
    "router", "mask", "a", "b", "size")}
ROUTEE_SPEC = {k: ((), jnp.int32) for k in (
    "hits", "sum1", "sum2", "sum3", "peak_total", "last_hits", "last_max")}


def make_pool_producer(mask_period: int = 16):
    """Producer behavior: at step t it tells the router whose ref it holds
    `[1, a, b, size]` iff bit `t mod mask_period` of its `mask` is set."""

    @behavior("producer", PRODUCER_SPEC, always_on=True)
    def pool_producer(state, inbox, ctx):
        tells = (state["mask"] >> (ctx.step % mask_period)) & 1
        job = jnp.stack([jnp.ones((), jnp.int32), state["a"], state["b"],
                         state["size"]])
        return {}, Emit.single(state["router"], job, 1, PAYLOAD_W,
                               when=tells > 0)

    return pool_producer


@behavior("routee", ROUTEE_SPEC)
def pool_routee(state, inbox, ctx):
    """Count, a running total of payload columns 1..3 and the max of column
    3 (the job's size), all int32 as the fan-in's collectors, and what the
    last step that brought anything brought."""
    top = inbox.max[3].astype(jnp.int32)
    new = {f"sum{j}": state[f"sum{j}"] + inbox.sum[j].astype(jnp.int32)
           for j in (1, 2, 3)}
    new.update(hits=state["hits"] + inbox.count,
               peak_total=state["peak_total"] + top,
               last_hits=inbox.count, last_max=top)
    return new, Emit.none(1, PAYLOAD_W)


def router_producers(n_producers: int, router: int, seed: int = 0,
                     levels: int = 8, mask_period: int = 16) -> dict:
    """A deployment's producers from a seed, as the columns of
    `PRODUCER_SPEC`: the router's ref, a mask of `mask_period` bits each set
    with probability 1/2 (the steps of the period on which it tells), and
    three integers below `levels`."""
    rng = np.random.default_rng([int(seed), n_producers, mask_period])
    return {"router": np.full(n_producers, router),
            "mask": rng.integers(0, 1 << mask_period, n_producers),
            "a": rng.integers(0, levels, n_producers),
            "b": rng.integers(0, levels, n_producers),
            "size": rng.integers(0, levels, n_producers)}


def build_router_pool(n_producers: int = 1 << 20, n_routees: int = 100_000,
                      logic: str = "round-robin", producers=None,
                      seed: int = 0, mask_period: int = 16,
                      delivery: str = "auto", delivery_backend=None):
    """Config 4: a pool of `n_routees` routees in rows [0, n_routees), its
    router in the next row, `n_producers` producers after it, each spawned
    with the router's ref in its `router` column. `producers` gives every
    producer's state (the columns of `PRODUCER_SPEC`); without it
    `router_producers(seed=seed)` draws one. The inbox has
    n_routees + 1 + n_producers + 8 rows (an emission slot a row, the
    host's eight)."""
    from ..routing.batched import BatchedRouter

    pool = BatchedRouter(logic, row=n_routees, routee_base=0,
                         n_routees=n_routees, payload_width=PAYLOAD_W)
    if producers is None:
        producers = router_producers(n_producers, pool.row, seed,
                                     mask_period=mask_period)
    producers = {k: np.asarray(producers[k], np.int32) for k in PRODUCER_SPEC}
    producer = make_pool_producer(mask_period)
    sys = BatchedSystem(capacity=n_routees + 1 + n_producers,
                        behaviors=[pool_routee, pool.behavior, producer],
                        payload_width=PAYLOAD_W, host_inbox=8,
                        need_max=True, delivery=delivery,
                        delivery_backend=delivery_backend, routers=[pool])
    sys.spawn_block(pool_routee, n_routees)
    (ref,) = sys.spawn_block(pool.behavior, 1)
    assert ref == pool.row
    sys.spawn_block(producer, n_producers, init_state=producers)
    return sys


def router_pool_left_behind(sys) -> dict:
    """What a run left in `build_router_pool`'s system, by kind of row:
    every column of the routees and of the producers, the router's counters
    (`routed` modulo 2^32), the inbox as it stands and the drop counters
    summed: the shape benchmark/reference/router.py judges."""
    (pool,) = sys.routers
    (counters,) = sys.read_routers()
    producers = slice(pool.row + 1, sys.capacity)
    return {"routees": {k: sys.read_state(k)[:pool.n_routees]
                        for k in ROUTEE_SPEC},
            "router": {k: counters[k] for k in ("next", "routed")},
            "producers": {k: sys.read_state(k)[producers]
                          for k in PRODUCER_SPEC},
            "inbox_dst": np.asarray(sys.inbox_dst),
            "inbox_payload": np.asarray(sys.inbox_payload),
            "inbox_valid": np.asarray(sys.inbox_valid),
            "dropped": sys.dropped_messages + sys.mailbox_overflow}


# --- the bank: ordered mailboxes as a deployment (sharded entities that are
# state machines: accounts, carts, orders, device twins; the Akka docs'
# AccountEntity). A teller holds in its state which account it tells at which
# step of a period; an account applies Deposit and Withdraw-if-sufficient to
# its balance ONE COMMAND AT A TIME, in mailbox order, so the order decides
# the result: no sum, count or max of a mailbox gives it.
DEPOSIT, WITHDRAW = 1, 2  # the message's type tag; 0 is an empty slot's
ACCOUNT_SPEC = {k: ((), jnp.int32) for k in (
    "balance", "deposited", "withdrawn", "rejected", "applied",
    "last_teller")}
TELLER_SPEC = {k: ((), jnp.int32) for k in ("home", "stride", "mask", "c")}


@behavior("account", ACCOUNT_SPEC, inbox="slots")
def bank_account(state, mailbox: Mailbox, ctx):
    """Folds the mailbox in slot order. Deposit(a): `balance += a`,
    `deposited += a`. Withdraw(a): if `balance >= a` then `balance -= a`,
    `withdrawn += a`, else `rejected += 1`. Every command: `applied += 1`,
    `last_teller` = the sender's row (payload column 1; column 0 is the
    amount). All int32; tell-only, the account sends nothing back."""
    cols = tuple(ACCOUNT_SPEC)

    def apply(carry, kind, pl):
        balance, deposited, withdrawn, rejected, applied, _ = carry
        amount = pl[0].astype(jnp.int32)
        deposit = kind == DEPOSIT
        enough = balance >= amount
        moved = jnp.where(deposit, amount, jnp.where(enough, -amount, 0))
        return (balance + moved,
                deposited + jnp.where(deposit, amount, 0),
                withdrawn + jnp.where(~deposit & enough, amount, 0),
                rejected + (~deposit & ~enough).astype(jnp.int32),
                applied + 1, pl[1].astype(jnp.int32))

    new = mailbox.fold(tuple(state[k] for k in cols), apply)
    return dict(zip(cols, new)), Emit.none(1, PAYLOAD_W)


def make_bank_teller(n_accounts: int, period: int = 16, levels: int = 8):
    """Teller behavior: at step t, with p = t mod `period`, it tells account
    `(home + stride * p) mod n_accounts` a Withdraw if bit p of its `mask`
    is set, else a Deposit, of amount `1 + ((c + t) mod levels)` for a
    Deposit and `2 + ((c + t) mod levels)` for a Withdraw; the payload is
    `[amount, its own row, 0, 0]`."""

    @behavior("teller", TELLER_SPEC, always_on=True)
    def bank_teller(state, inbox, ctx):
        t = ctx.step
        p = t % period
        account = (state["home"] + state["stride"] * p) % n_accounts
        withdraw = (state["mask"] >> p) & 1
        amount = 1 + withdraw + (state["c"] + t) % levels
        command = jnp.stack([amount, ctx.actor_id, jnp.zeros((), jnp.int32),
                             jnp.zeros((), jnp.int32)])
        return {}, Emit.single(account, command, 1, PAYLOAD_W,
                               mtype=jnp.where(withdraw > 0, WITHDRAW,
                                               DEPOSIT))

    return bank_teller


def bank_tellers(n_tellers: int, n_accounts: int, seed: int = 0,
                 period: int = 16, levels: int = 8) -> dict:
    """A deployment's tellers from a seed, as the columns of `TELLER_SPEC`:
    `home` uniform over the accounts, an odd `stride` below their number, a
    `mask` of `period` bits each set with probability 1/2, `c` below
    `levels`."""
    rng = np.random.default_rng([int(seed), n_tellers, n_accounts])
    return {"home": rng.integers(0, n_accounts, n_tellers),
            "stride": 2 * rng.integers(0, max(n_accounts // 2, 1),
                                       n_tellers) + 1,
            "mask": rng.integers(0, 1 << period, n_tellers),
            "c": rng.integers(0, levels, n_tellers)}


def build_bank(n_tellers: int = 1 << 20, n_accounts: int = 1 << 17,
               mailbox_slots: int = 16, spill_capacity: int = 1 << 14,
               tellers=None, seed: int = 0, period: int = 16,
               levels: int = 8, delivery_backend=None):
    """Accounts in rows [0, n_accounts), tellers in the next n_tellers rows.
    `tellers` gives every teller's state (the columns of `TELLER_SPEC`);
    without it `bank_tellers(seed=seed)` draws one. A mixed system: the
    accounts take ordered mailboxes of `mailbox_slots` slots, the tellers a
    reduce inbox nobody writes to. The inbox has spill_capacity +
    n_accounts + n_tellers + 8 rows: the spill region FIRST, so that what an
    account could not take in one step it takes ahead of the next step's
    fresh mail, then an emission slot a row, then the host's eight."""
    if tellers is None:
        tellers = bank_tellers(n_tellers, n_accounts, seed, period, levels)
    tellers = {k: np.asarray(tellers[k], np.int32) for k in TELLER_SPEC}
    teller = make_bank_teller(n_accounts, period, levels)
    sys = BatchedSystem(capacity=n_accounts + n_tellers,
                        behaviors=[bank_account, teller],
                        payload_width=PAYLOAD_W, out_degree=1, host_inbox=8,
                        mailbox_slots=mailbox_slots,
                        spill_capacity=spill_capacity,
                        delivery_backend=delivery_backend)
    sys.spawn_block(bank_account, n_accounts)
    sys.spawn_block(teller, n_tellers, init_state=tellers)
    return sys


def bank_left_behind(sys, n_accounts: int) -> dict:
    """What a run left in `build_bank`'s system: every column of the
    accounts and of the tellers, the inbox as it stands (the spill region
    first), the step count, and the counters of what was lost and of what
    the spill carried: the shape benchmark/reference/bank.py judges."""
    accounts = {k: sys.read_state(k)[:n_accounts] for k in ACCOUNT_SPEC}
    spilled, high = sys.read_spill()
    return {"accounts": accounts,
            "tellers": {k: sys.read_state(k)[n_accounts:]
                        for k in TELLER_SPEC},
            "inbox_dst": np.asarray(sys.inbox_dst),
            "inbox_type": np.asarray(sys.inbox_type),
            "inbox_payload": np.asarray(sys.inbox_payload),
            "inbox_valid": np.asarray(sys.inbox_valid),
            "steps": int(np.asarray(sys.step_count)),
            "dropped": sys.dropped_messages + sys.mailbox_overflow,
            "spilled": spilled, "spill_high_water": high}


# --- the bank, cluster-sharded (the Akka documentation's Cluster Sharding
# example IS the AccountEntity): an entity's id decides its logical shard
# (HashCodeMessageExtractor: id mod shards), the shards are spread evenly
# over the regions (one a chip), and a sender addresses an entity by id
# wherever the sender sits, every command through the exchange. Here that is
# arithmetic on the id, since a row of the actor space IS an address: chip,
# then position on the chip. (Below everything the one-chip cells trace, so
# that their lowered programs keep their source locations.)
class BankShardedLayout:
    """Where `build_bank_sharded` puts what. Chip c holds the rows
    [c * rows_a_chip, (c + 1) * rows_a_chip): first its accounts, logical
    shard by logical shard (shard s lives on chip s div shards_a_chip and
    holds the accounts s, s + n_shards, s + 2 * n_shards, ... in that
    order), then its tellers (teller i on chip i div tellers_a_chip)."""

    def __init__(self, n_tellers: int, n_accounts: int, n_devices: int,
                 n_shards: int):
        if n_shards % n_devices or n_accounts % n_shards \
                or n_tellers % n_devices:
            raise ValueError(
                f"{n_accounts} accounts in {n_shards} logical shards and "
                f"{n_tellers} tellers do not spread evenly over "
                f"{n_devices} chips")
        self.n_tellers, self.n_accounts = n_tellers, n_accounts
        self.n_devices, self.n_shards = n_devices, n_shards
        self.shards_a_chip = n_shards // n_devices
        self.accounts_a_shard = n_accounts // n_shards
        self.accounts_a_chip = n_accounts // n_devices
        self.tellers_a_chip = n_tellers // n_devices
        self.rows_a_chip = self.accounts_a_chip + self.tellers_a_chip

    def account_row(self, account):
        """The row of the account with this id: int arithmetic only, so it
        takes a traced id inside a behavior and a numpy array outside."""
        shard = account % self.n_shards
        return (shard // self.shards_a_chip) * self.rows_a_chip \
            + (shard % self.shards_a_chip) * self.accounts_a_shard \
            + account // self.n_shards

    def teller_row(self, teller):
        return (teller // self.tellers_a_chip) * self.rows_a_chip \
            + self.accounts_a_chip + teller % self.tellers_a_chip


def make_bank_teller_sharded(layout: BankShardedLayout, period: int = 16,
                             levels: int = 8):
    """`make_bank_teller`'s teller, telling the same account the same
    command: the rule is that function's, and the address the account's ROW
    under `layout`, computed from its id."""
    plain = make_bank_teller(layout.n_accounts, period, levels)

    @behavior("teller", TELLER_SPEC, always_on=True)
    def bank_teller(state, inbox, ctx):
        new_state, emit = plain.receive(state, inbox, ctx)
        return new_state, emit._replace(dst=layout.account_row(emit.dst))

    return bank_teller


def build_bank_sharded(n_tellers: int = 1 << 20, n_accounts: int = 1 << 17,
                       n_devices=None, n_shards: int = 256,
                       mailbox_slots: int = 16, spill_capacity: int = 1 << 12,
                       remote_capacity_per_pair=None, tellers=None,
                       seed: int = 0, period: int = 16, levels: int = 8,
                       delivery_backend=None):
    """`build_bank`'s deployment over a mesh, laid out by
    `BankShardedLayout` (kept on the system as `.layout`). The tellers keep
    their relative order (teller i's row grows with i), so every account
    meets the same commands in the same order as `build_bank`'s from the
    same `tellers`, wherever it lives. Every command rides the exchange:
    with hashed ids one in `n_devices` stays on its chip, in the chip's own
    chunk. `spill_capacity` and `remote_capacity_per_pair` are a chip's and
    a pair's; the latter unset is lossless (a chip's whole row count)."""
    import jax as _jax
    if n_devices is None:
        n_devices = len(_jax.devices())
    lay = BankShardedLayout(n_tellers, n_accounts, n_devices, n_shards)
    if tellers is None:
        tellers = bank_tellers(n_tellers, n_accounts, seed, period, levels)
    teller = make_bank_teller_sharded(lay, period, levels)
    sys = ShardedBatchedSystem(
        capacity=n_accounts + n_tellers, behaviors=[bank_account, teller],
        n_devices=n_devices, payload_width=PAYLOAD_W, out_degree=1,
        host_inbox_per_shard=8, mailbox_slots=mailbox_slots,
        spill_capacity=spill_capacity,
        remote_capacity_per_pair=remote_capacity_per_pair,
        delivery_backend=delivery_backend)
    rows = np.arange(sys.capacity)
    is_teller = rows % lay.rows_a_chip >= lay.accounts_a_chip
    at = lay.teller_row(np.arange(n_tellers))
    init = {}
    for k in TELLER_SPEC:
        init[k] = np.zeros(sys.capacity, np.int32)
        init[k][at] = np.asarray(tellers[k], np.int32)
    sys.spawn_layout(is_teller.astype(np.int32), init)
    sys.layout = lay
    return sys


def bank_sharded_left_behind(sys) -> dict:
    """What a run left in `build_bank_sharded`'s system, in the shape
    `bank_left_behind` gives (and benchmark/reference/bank_sharded.py
    judges): the accounts' columns BY ACCOUNT ID and the tellers' by teller
    index, `last_teller` as the system holds it (a row; `.layout` maps it),
    the inbox as it stands, a block a chip (spill region, then a chunk a
    source chip, then the host's rows), the step count, and the counters:
    what was lost (`dropped`: exchange, and the spill regions' overflow),
    the exchange's part of it, and `read_mesh_stats()`'s three."""
    lay = sys.layout
    account_at = lay.account_row(np.arange(lay.n_accounts))
    teller_at = lay.teller_row(np.arange(lay.n_tellers))
    return {"accounts": {k: sys.read_state(k)[account_at]
                         for k in ACCOUNT_SPEC},
            "tellers": {k: sys.read_state(k)[teller_at]
                        for k in TELLER_SPEC},
            "inbox_dst": np.asarray(sys.inbox_dst),
            "inbox_type": np.asarray(sys.inbox_type),
            "inbox_payload": np.asarray(sys.inbox_payload),
            "inbox_valid": np.asarray(sys.inbox_valid),
            "steps": int(np.asarray(sys.step_count)),
            "dropped": sys.total_dropped + sys.mailbox_overflow,
            "exchange_dropped": sys.total_dropped,
            **sys.read_mesh_stats()}


def make_crossshard_behavior(local_n: int):
    """Entity that forwards its token to the SAME slot in the next device
    shard — every single message crosses the mesh (all_to_all hot path)."""

    @behavior("xshard", {"received": ((), jnp.int32)})
    def xshard(state, inbox, ctx):
        nxt = (ctx.actor_id + local_n) % ctx.n_actors
        return ({"received": state["received"] + inbox.count},
                Emit.single(nxt, inbox.sum, 1, PAYLOAD_W,
                            when=inbox.count > 0))

    return xshard


def build_cross_shard(n_shards: int = 256, entities_per_shard: int = 4096,
                      n_devices=None):
    """Config 5: 256 logical shards x 4k entities on the device mesh with
    cross-shard tells (sharding/ShardRegion.scala:1046 deliverMessage as an
    all_to_all). Logical shards are folded onto the devices; every tell hops
    one device shard, so all traffic rides the exchange."""
    import jax as _jax
    n = n_shards * entities_per_shard
    if n_devices is None:
        n_devices = len(_jax.devices())
    if n % n_devices:
        n += n_devices - n % n_devices
    b = make_crossshard_behavior(n // n_devices)
    sys = ShardedBatchedSystem(capacity=n, behaviors=[b],
                               n_devices=n_devices, payload_width=PAYLOAD_W,
                               host_inbox_per_shard=8)
    sys.spawn_block(b, n)
    return sys


def build_ping_pong():
    @behavior("pp", {"hits": ((), jnp.int32)})
    def pp(state, inbox, ctx):
        other = 1 - ctx.actor_id
        return ({"hits": state["hits"] + inbox.count},
                Emit.single(other, inbox.sum, 1, PAYLOAD_W, when=inbox.count > 0))

    sys = BatchedSystem(capacity=2, behaviors=[pp], payload_width=PAYLOAD_W,
                        host_inbox=8)
    sys.spawn_block(pp, 2)
    return sys
