"""Plain reference for Savina's Bank Transaction over C chips: numpy and
Python only, nothing of the program. The account's rule, one message at a
time with its stash and its budget, is benchmark/reference/bank_transfer.py's
(`handle`, `account_turns`, `transfers`); what is here is the cluster: where
an entity lives, which chip's queue a message waits in, and in which order.

Placement (the Akka documentation's Cluster Sharding, as
benchmark/reference/bank_sharded.py writes it down: an entity's id decides
its shard, id mod S; the S shards spread evenly over the C chips). With A
accounts, T tellers and N = (A + T) / C rows a chip, chip c holds the rows
[c N, (c + 1) N):

    account a:  shard s = a mod S,  chip c = s div (S / C),
                row  c N + (s mod (S / C)) (A / S) + a div S
    teller i:   chip c = i div (T / C),  row  c N + A / C + i mod (T / C)

A message is a tuple (dst, type, a0, a1, a2, a3) of ints, `dst` the ROW it
is told to. Credit[amount, dst, teller, k] names its destination by ID and
its teller by ROW; the source tells Debit[amount, its own ID] to the row of
dst; the destination tells Reply to the row of the source's id; the source
tells Reply[k] to the teller's row. Teller i tells transfer k's Credit to the
row of src (`transfers`, the one-chip rule over A accounts).

A step, on every chip c at once:

1. c's accounts take their queue: what they left over at earlier steps, in
   the order it had, then the last step's fresh mail. Each account with mail
   or an open stash runs `account_turns` (the stash first, then the first
   `mailbox_slots` of its queue, under the budget of `emit_width` tells),
   the accounts in ROW order; what each leaves goes, in order, to the chip's
   leftovers, of which the first `spill_capacity` are kept (the rest are
   LOST and counted, `mail_dropped`).
2. c's tellers read the Replies delivered to them and tell their next Credit
   (all at step 0).
3. What c tells, in the sender's row order (its accounts by row, each in the
   order it told, then its tellers), goes to the chip of each message's row.
   A pair (source chip, destination chip) carries at most `pair_capacity`
   messages a step: the first in that order; the rest are LOST and counted
   (`exchange_dropped`).

So chip c's next queue is its kept leftovers, then the fresh mail of source
chip 0, 1, ..., C - 1 in turn: by the SENDER'S GLOBAL ROW, which is what its
inbox block holds (spill region, a chunk a source chip, the host's rows), and
`judge` compares it row for row. The counters, as the program names them:
`stashed`, `stash_overflow` (lost by a full stash), `retained_by_budget`
(mail the budget left), `spilled` (leftovers kept), `mail_dropped`,
`exchange_dropped` (summed), `stash_high_water`, `emit_high_water` (the most
one row told in a step, a teller's one included), `spill_high_water` (the
most one chip kept), `exchange_high_water` (the most one pair was asked to
carry in a step, before the cut). Totals are int32 in the system and wrap:
compared modulo 2^32."""

from __future__ import annotations

import numpy as np

from benchmark.reference import bank_transfer as one_chip

CREDIT, DEBIT, REPLY = one_chip.CREDIT, one_chip.DEBIT, one_chip.REPLY
ACCOUNT_COLUMNS, TELLER_COLUMNS = (one_chip.ACCOUNT_COLUMNS,
                                   one_chip.TELLER_COLUMNS)
WIDTH, WRAP = one_chip.WIDTH, one_chip.WRAP
seed_keys, signed = one_chip.seed_keys, one_chip.signed
SUMS = ("stashed", "stash_overflow", "retained_by_budget", "spilled",
        "mail_dropped", "exchange_dropped")
HIGHS = ("stash_high_water", "emit_high_water", "spill_high_water",
         "exchange_high_water")
COUNTERS = SUMS + HIGHS
# which of the numbers `judge` gives a counter's difference is counted in
STASH_COUNTERS = ("stashed", "stash_high_water")


class Deployment:
    """The configuration's numbers, and the placement they give."""

    def __init__(self, n_accounts: int, n_tellers: int, chips: int,
                 shards: int, slots: int, emit_width: int,
                 stash_capacity: int, spill_capacity: int,
                 pair_capacity: int, host_rows: int):
        self.n_accounts, self.n_tellers = int(n_accounts), int(n_tellers)
        self.chips, self.shards = int(chips), int(shards)
        self.pair_capacity, self.host_rows = int(pair_capacity), int(host_rows)
        self.rule = one_chip.Rule(n_accounts, slots, emit_width,
                                  stash_capacity, spill_capacity)
        a_chip, t_chip = self.n_accounts // chips, self.n_tellers // chips
        self.rows_a_chip = a_chip + t_chip
        self.block_rows = spill_capacity + chips * self.pair_capacity \
            + self.host_rows
        a = np.arange(self.n_accounts)
        shard = a % shards
        chip = shard // (shards // chips)
        self.account_row = chip * self.rows_a_chip \
            + (shard % (shards // chips)) * (self.n_accounts // shards) \
            + a // shards
        i = np.arange(self.n_tellers)
        self.teller_row = (i // t_chip) * self.rows_a_chip + a_chip \
            + i % t_chip
        rows = self.rows_a_chip * chips
        self.account_at = np.full(rows, -1, np.int64)
        self.account_at[self.account_row] = a
        self.teller_at = np.full(rows, -1, np.int64)
        self.teller_at[self.teller_row] = i
        # Python lists: the step looks them up a message at a time
        self.row_of = self.account_row.tolist()
        self.id_at = self.account_at.tolist()

    def chip_of(self, row: int) -> int:
        return row // self.rows_a_chip

    def handle(self, acc, a, msg):
        """`one_chip.handle` for account `a` (an id), its tell addressed to
        a ROW: a Debit to the destination's, a Reply to the source's; a
        Reply to the teller carries the row the Credit named."""
        told = one_chip.handle(acc, a, msg)
        if told is None or msg[1] == REPLY:
            return told
        return (self.row_of[told[0]],) + told[1:]


def from_config(conf: dict, chips: int) -> Deployment:
    return Deployment(conf["accounts"], conf["tellers"], chips,
                      conf["logical_shards"], conf["mailbox_slots"],
                      conf["emit_width"], conf["stash_capacity"],
                      conf["spill_capacity"], conf["remote_capacity_per_pair"],
                      conf["host_inbox"])


# -------------------------------------------------------------- the world
class World:
    """Everything the rule reads: accounts (a Python list a column, by id),
    stashes (id -> its messages, oldest first, non-empty only), tellers
    (numpy, int64, by index), a queue a chip, the step, and how the newest
    step laid every chip's queue out (`rows[c]`: leftovers kept, then a
    count a source chip)."""

    def __init__(self, accounts, stash, tellers, queues, t, rows=None):
        self.accounts = {k: [int(x) for x in np.asarray(accounts[k])]
                         for k in ACCOUNT_COLUMNS}
        self.stash = {a: list(s) for a, s in stash.items() if s}
        self.tellers = {k: np.asarray(tellers[k], np.int64).copy()
                        for k in TELLER_COLUMNS}
        self.queues = [list(q) for q in queues]
        self.t = int(t)
        self.rows = rows or [[0] * (len(queues) + 1) for _ in queues]

    @staticmethod
    def start(keys, dep: Deployment) -> "World":
        zero = np.zeros(dep.n_tellers, np.int64)
        return World({k: np.zeros(dep.n_accounts, np.int64)
                      for k in ACCOUNT_COLUMNS}, {},
                     {"key": np.asarray(keys, np.int64), "k": zero,
                      "done": zero, "mismatch": zero},
                     [[] for _ in range(dep.chips)], 0)


def zero_counters() -> dict:
    return dict.fromkeys(COUNTERS, 0)


def _credits(world: World, dep: Deployment, replies: dict) -> list:
    """The tellers' step: the Replies delivered (teller index -> messages),
    then the next transfer's Credit of every teller that heard one (all at
    step 0), by teller index, a Credit to the row of its source."""
    tel = world.tellers
    got = np.zeros(dep.n_tellers, np.int64)
    said = np.zeros(dep.n_tellers, np.int64)
    for i, msgs in replies.items():
        got[i] = len(msgs)
        said[i] = sum(m[2] for m in msgs)
    heard = got > 0
    right = (got == 1) & (said == tel["k"])
    tel["done"] += heard & right
    tel["mismatch"] += heard & ~right
    tel["k"] += heard
    telling = np.flatnonzero(heard | (world.t == 0))
    src, dst, amount = one_chip.transfers(
        tel["key"][telling], tel["k"][telling], dep.n_accounts)
    return [(dep.row_of[s], CREDIT, int(m), int(d), int(r), int(k))
            for s, d, m, r, k in zip(src, dst, amount, dep.teller_row[telling],
                                     tel["k"][telling])]


def step(world: World, dep: Deployment, counters: dict,
         turns=one_chip.account_turns, handle=None, chunk_order=None,
         capacity_of=None, fresh_first=False) -> dict:
    """One literal step of every chip, in place; the counters are added to
    in place. The keywords are the controls': `turns` and `handle` an
    account's rule, `chunk_order` the order of the source chips' chunks,
    `capacity_of(source, destination)` a pair's capacity, `fresh_first` the
    fresh mail ahead of the leftovers. Returns the step's own counts
    (`replayed`: messages handled out of a stash; `left`: leftovers
    kept)."""
    rule = dep.rule
    handle = handle or dep.handle
    counts = {"stashed": 0, "lost": 0, "retained": 0, "replayed": 0}
    told, kept = [], []
    replies = {}
    id_at, row_of, n = dep.id_at, dep.row_of, dep.rows_a_chip
    in_reply = world.accounts["in_reply"]
    opened = [set() for _ in world.queues]  # a chip's open stashes
    for a in world.stash:
        if not in_reply[a]:
            opened[row_of[a] // n].add(a)
    for c, queue in enumerate(world.queues):
        boxes = {}
        for msg in queue:
            a = id_at[msg[0]]
            if a >= 0:
                boxes.setdefault(a, []).append(msg)
            else:
                replies.setdefault(int(dep.teller_at[msg[0]]), []).append(msg)
        busy = opened[c].union(boxes)
        left, out = [], []
        for a in sorted(busy, key=row_of.__getitem__):
            box = boxes.get(a, [])
            before = len(out)
            rest = turns(world, rule, a, box, out, counts, handle)
            counters["emit_high_water"] = max(counters["emit_high_water"],
                                              len(out) - before)
            counts["retained"] += len(rest) - max(len(box) - rule.slots, 0)
            left.extend(rest)
        keep = min(len(left), rule.spill)
        counters["mail_dropped"] += len(left) - keep
        counters["spilled"] += keep
        counters["spill_high_water"] = max(counters["spill_high_water"],
                                           keep)
        kept.append(left[:keep])
        told.append(out)
    credits = _credits(world, dep, replies)
    if credits:
        counters["emit_high_water"] = max(counters["emit_high_water"], 1)
    for msg in credits:  # a chip's tellers' rows follow its accounts'
        told[dep.chip_of(msg[4])].append(msg)
    # what each source chip tells each destination chip, in its order
    pairs = [[[] for _ in told] for _ in told]
    for s, out in enumerate(told):
        to = pairs[s]
        for m in out:
            to[m[0] // n].append(m)
    queues, rows = [], []
    for d in range(dep.chips):
        fresh, layout = [], [len(kept[d])]
        for s in (range(dep.chips) if chunk_order is None else chunk_order):
            mine = pairs[s][d]
            cap = dep.pair_capacity if capacity_of is None \
                else capacity_of(s, d)
            counters["exchange_high_water"] = max(
                counters["exchange_high_water"], len(mine))
            counters["exchange_dropped"] += max(len(mine) - cap, 0)
            fresh.extend(mine[:cap])
            layout.append(min(len(mine), cap))
        queues.append(fresh + kept[d] if fresh_first else kept[d] + fresh)
        rows.append(layout)
    world.queues, world.rows = queues, rows
    counts["left"] = sum(len(k) for k in kept)
    counters["stashed"] += counts["stashed"]
    counters["stash_overflow"] += counts["lost"]
    counters["retained_by_budget"] += counts["retained"]
    counters["stash_high_water"] = max(
        [counters["stash_high_water"]]
        + [len(s) for s in world.stash.values()])
    world.t += 1
    return counts


def replay(world: World, dep: Deployment, steps: int, **faults) -> dict:
    """`steps` steps on, in place; returns the counters of these steps
    alone, and the summed `replayed`."""
    counters = dict(zero_counters(), replayed=0)
    for _ in range(steps):
        counts = step(world, dep, counters, **faults)
        counters["replayed"] += counts["replayed"]
    return counters


# --------------------------------- the same rule, every account at once
# `step` handles one message at a time. `replay_fast` takes the same turns in
# rounds: round r handles the r-th message of every account that still has
# something to do (an account handles at most `mailbox_slots` + `emit_width`
# a step), with the world as arrays; what it tells is put back in `step`'s
# order by (sender row, round), and the exchange and the leftovers are cut
# as `step` cuts them. tests/benchmark/test_benchmark_xtransfer.py holds the
# two to the same world after every step, capacities too small included.
# `judge` replays with it; the controls break `step`.
class _Arrays:
    """A World as arrays: accounts by id, the stashes as rings (`stype`,
    `spay` [A, C], `shead`, `ssize`), the tellers, every chip's queue one
    after the other (`q`: [M, 6] int64), and `sizes`: each chip's count."""

    def __init__(self, world: World, dep: Deployment):
        a, cap = dep.n_accounts, dep.rule.cap
        self.acc = {k: np.asarray(world.accounts[k], np.int64)
                    for k in ACCOUNT_COLUMNS}
        self.stype = np.zeros((a, cap), np.int32)
        self.spay = np.zeros((a, cap, WIDTH), np.int32)
        self.shead = np.zeros(a, np.int64)
        self.ssize = np.zeros(a, np.int64)
        for b, msgs in world.stash.items():
            self.ssize[b] = len(msgs)
            for j, m in enumerate(msgs):
                self.stype[b, j], self.spay[b, j] = m[1], m[2:]
        self.tellers = world.tellers
        self.q = np.asarray([m for queue in world.queues for m in queue],
                            np.int64).reshape(-1, 2 + WIDTH)
        self.sizes = [len(queue) for queue in world.queues]
        self.t, self.rows = world.t, world.rows

    def into(self, world: World, dep: Deployment) -> None:
        world.accounts = {k: v.tolist() for k, v in self.acc.items()}
        cap = dep.rule.cap
        world.stash = {}
        for b in np.flatnonzero(self.ssize > 0).tolist():
            at = (self.shead[b] + np.arange(self.ssize[b])) % cap
            world.stash[b] = [(dep.row_of[b], int(self.stype[b, j]))
                              + tuple(int(x) for x in self.spay[b, j])
                              for j in at]
        world.tellers = self.tellers
        rows = [tuple(int(x) for x in m) for m in self.q]
        world.queues, at = [], 0
        for n in self.sizes:
            world.queues.append(rows[at:at + n])
            at += n
        world.t, world.rows = self.t, self.rows


def _rounds(st: _Arrays, dep: Deployment, counters: dict, counts: dict):
    """Every account's turns of one step, round by round. Returns the
    messages told, each with its sender's row and round, and what the
    accounts leave over, each with its account's row and place."""
    rule, q = dep.rule, st.q
    S, K, cap = rule.slots, rule.budget, rule.cap
    acc, row_of = st.acc, dep.account_row
    dst_acct = dep.account_at[q[:, 0]] if len(q) else np.zeros(0, np.int64)
    mine = np.flatnonzero(dst_acct >= 0)
    order = mine[np.argsort(dst_acct[mine], kind="stable")]
    boxed = dst_acct[order]  # an account's messages in queue order
    n_box = np.bincount(boxed, minlength=dep.n_accounts)
    start = np.concatenate([[0], np.cumsum(n_box)[:-1]])
    mail = np.minimum(n_box, S)
    busy = np.flatnonzero((n_box > 0)
                          | ((st.ssize > 0) & (acc["in_reply"] == 0)))
    took = np.zeros(busy.shape[0], np.int64)  # mail taken or stashed
    used = np.zeros(busy.shape[0], np.int64)  # told this step
    live = np.ones(busy.shape[0], bool)
    told, r = [], 0
    while live.any():
        act = np.flatnonzero(live)
        a = busy[act]
        waiting = acc["in_reply"][a] == 1
        open_ = (st.ssize[a] > 0) & ~waiting
        has = ~open_ & (took[act] < mail[a])
        at = order[np.minimum(start[a] + took[act], max(len(order) - 1, 0))] \
            if len(order) else np.zeros(a.shape[0], np.int64)
        msg = q[at] if len(q) else np.zeros((a.shape[0], 2 + WIDTH), np.int64)
        push = has & waiting & (msg[:, 1] != REPLY)
        tells = (msg[:, 1] != REPLY) | waiting
        take = has & ~push & (used[act] + tells <= K)
        replay = open_ & (used[act] + 1 <= K)
        live[act[~(push | take | replay)]] = False
        # stashed, or lost by a full stash
        pa, pm = a[push], msg[push]
        room = st.ssize[pa] < cap
        counts["stashed"] += int(room.sum())
        counts["lost"] += int((~room).sum())
        pa, pm = pa[room], pm[room]
        slot = (st.shead[pa] + st.ssize[pa]) % cap
        st.stype[pa, slot], st.spay[pa, slot] = pm[:, 1], pm[:, 2:]
        st.ssize[pa] += 1
        took[act[push | take]] += 1
        # handled: the stash's oldest, or the mail's next
        ra = a[replay]
        head = st.shead[ra]
        rm = np.concatenate([row_of[ra][:, None], st.stype[ra, head][:, None],
                             st.spay[ra, head]], axis=1).astype(np.int64)
        st.shead[ra] = (head + 1) % cap
        st.ssize[ra] -= 1
        counts["replayed"] += int(ra.shape[0])
        h = np.concatenate([ra, a[take]])
        hm = np.concatenate([rm, msg[take]])
        kind, a0, a1, a2, a3 = (hm[:, j] for j in range(1, 6))
        credit, debit = kind == CREDIT, kind == DEBIT
        answered = (kind == REPLY) & (acc["in_reply"][h] == 1)
        teller, tk = acc["teller"][h], acc["tk"][h]
        acc["balance"][h] += np.where(debit, a0, 0) - np.where(credit, a0, 0)
        acc["teller"][h] = np.where(credit, a2, teller)
        acc["tk"][h] = np.where(credit, a3, tk)
        acc["in_reply"][h] = np.where(credit, 1, np.where(
            answered, 0, acc["in_reply"][h]))
        acc["credits"][h] += credit
        acc["debits"][h] += debit
        acc["replies"][h] += answered
        acc["violations"][h] += (kind == REPLY) & ~answered
        sent = credit | debit | answered
        zero = np.zeros_like(a0)
        out = np.stack([
            np.where(answered, teller, row_of[np.where(answered, 0, a1)]),
            np.where(credit, DEBIT, REPLY),
            np.where(credit, a0, np.where(answered, tk, 0)),
            np.where(credit, h, 0), zero, zero], axis=1)[sent]
        pos = np.concatenate([act[replay], act[take]])
        used[pos] += sent
        told.append((row_of[h[sent]], np.full(out.shape[0], r), out))
        r += 1
    counters["emit_high_water"] = max(counters["emit_high_water"],
                                      int(used.max(initial=0)))
    # what each account leaves: its box from the first message not taken on
    left_n = n_box[busy] - took
    counts["retained"] += int((mail[busy] - took).sum())
    who = np.repeat(busy, left_n)
    place = np.arange(who.shape[0]) - np.repeat(
        np.cumsum(left_n) - left_n, left_n) + np.repeat(took, left_n)
    left = q[order[start[who] + place]] if who.size \
        else np.zeros((0, 2 + WIDTH), np.int64)
    return told, row_of[who], place, left


def _fast_step(st: _Arrays, dep: Deployment, counters: dict) -> dict:
    """`step`, as arrays: one step of every chip, in place."""
    rule, n, chips = dep.rule, dep.rows_a_chip, dep.chips
    counts = {"stashed": 0, "lost": 0, "retained": 0, "replayed": 0}
    told, left_row, left_place, left = _rounds(st, dep, counters, counts)
    # the leftovers by account row, each account's in order; a chip keeps
    # the first `spill_capacity`
    by = np.lexsort((left_place, left_row))
    left, left_chip = left[by], left_row[by] // n
    first = np.searchsorted(left_chip, np.arange(chips + 1))
    rank = np.arange(left.shape[0]) - first[left_chip]
    keep = rank < rule.spill
    per_chip = np.minimum(np.diff(first), rule.spill)
    counters["mail_dropped"] += int((~keep).sum())
    counters["spilled"] += int(per_chip.sum())
    counters["spill_high_water"] = max(counters["spill_high_water"],
                                       int(per_chip.max(initial=0)))
    left, left_chip = left[keep], left_chip[keep]
    # the tellers: the Replies delivered to them, then their Credits
    q = st.q
    tid = dep.teller_at[q[:, 0]] if len(q) else np.zeros(0, np.int64)
    mine = tid >= 0
    tel = st.tellers
    got = np.bincount(tid[mine], minlength=dep.n_tellers)
    said = np.zeros(dep.n_tellers, np.int64)
    np.add.at(said, tid[mine], q[mine, 2])
    heard = got > 0
    right = (got == 1) & (said == tel["k"])
    tel["done"] += heard & right
    tel["mismatch"] += heard & ~right
    tel["k"] += heard
    telling = np.flatnonzero(heard | (st.t == 0))
    src, dst, amount = one_chip.transfers(
        tel["key"][telling], tel["k"][telling], dep.n_accounts)
    credits = np.stack([dep.account_row[src], np.full_like(src, CREDIT),
                        amount, dst, dep.teller_row[telling],
                        tel["k"][telling]], axis=1)
    if telling.size:
        counters["emit_high_water"] = max(counters["emit_high_water"], 1)
    # what every chip tells, in the sender's row order; then the exchange
    sender = np.concatenate([t[0] for t in told]
                            + [dep.teller_row[telling]])
    rnd = np.concatenate([t[1] for t in told]
                         + [np.zeros(telling.size, np.int64)])
    msgs = np.concatenate([t[2] for t in told] + [credits]).reshape(
        -1, 2 + WIDTH)
    by = np.lexsort((rnd, sender))
    msgs, src_chip = msgs[by], sender[by] // n
    pair = (msgs[:, 0] // n) * chips + src_chip
    by = np.argsort(pair, kind="stable")
    msgs, pair = msgs[by], pair[by]
    asked = np.bincount(pair, minlength=chips * chips)
    counters["exchange_high_water"] = max(counters["exchange_high_water"],
                                          int(asked.max(initial=0)))
    counters["exchange_dropped"] += int(np.maximum(
        asked - dep.pair_capacity, 0).sum())
    starts = np.concatenate([[0], np.cumsum(asked)[:-1]])
    carried = (np.arange(pair.shape[0]) - starts[pair]) < dep.pair_capacity
    msgs, pair = msgs[carried], pair[carried]
    queues, rows, sizes = [], [], []
    for d in range(chips):
        fresh = msgs[pair // chips == d]
        kept = left[left_chip == d]
        queues += [kept, fresh]
        sizes.append(kept.shape[0] + fresh.shape[0])
        rows.append([kept.shape[0]] + np.minimum(
            asked[d * chips:(d + 1) * chips], dep.pair_capacity).tolist())
    st.q = np.concatenate(queues).reshape(-1, 2 + WIDTH)
    st.sizes, st.rows = sizes, rows
    counters["stashed"] += counts["stashed"]
    counters["stash_overflow"] += counts["lost"]
    counters["retained_by_budget"] += counts["retained"]
    counters["stash_high_water"] = max(counters["stash_high_water"],
                                       int(st.ssize.max(initial=0)))
    st.t += 1
    return counts


def replay_fast(world: World, dep: Deployment, steps: int) -> dict:
    """`replay` without faults, a step over every account at once."""
    st = _Arrays(world, dep)
    counters = dict(zero_counters(), replayed=0)
    for _ in range(steps):
        counters["replayed"] += _fast_step(st, dep, counters)["replayed"]
    st.into(world, dep)
    return counters


def advance(before: dict, chunk: dict) -> dict:
    """The counters after a chunk, from those before it and the chunk's
    own: the sums added (modulo 2^32), the high waters the larger."""
    out = {k: (int(before[k]) + chunk[k]) % WRAP for k in SUMS}
    out.update({k: max(int(before[k]), chunk[k]) for k in HIGHS})
    return out


# ------------------------------------------------------------ a reading
def queues_of(snap: dict, dep: Deployment):
    """Every chip's inbox block as messages in row order (one_chip's test of
    a well-formed message, and a row on THAT chip), the count of valid rows
    that are not, and the layout: leftovers, then a count a source chunk."""
    queues, malformed, rows = [], 0, []
    valid_all = np.asarray(snap["inbox_valid"], bool)
    for c in range(dep.chips):
        at = slice(c * dep.block_rows, (c + 1) * dep.block_rows)
        block = {k: np.asarray(snap[k])[at] for k in (
            "inbox_dst", "inbox_type", "inbox_payload", "inbox_valid")}
        queue, bad = one_chip.queue_of(block)
        here = [m for m in queue if dep.chip_of(m[0]) == c]
        queues.append(here)
        malformed += bad + len(queue) - len(here)
        v = valid_all[at]
        sc = dep.rule.spill
        chunks = v[sc:sc + dep.chips * dep.pair_capacity].reshape(
            dep.chips, dep.pair_capacity).sum(1)
        rows.append([int(v[:sc].sum())] + [int(x) for x in chunks])
    return queues, malformed, rows


def stashes_of(snap: dict, dep: Deployment) -> dict:
    """id -> its stash as messages, oldest first (non-empty only), each
    addressed to the account's row, as it arrived."""
    st = snap["stash"]
    size = np.asarray(st["stash_size"], np.int64)
    head = np.asarray(st["stash_head"], np.int64)
    types = np.asarray(st["stash_type"], np.int64)
    payload = np.asarray(st["stash_payload"], np.float64)
    cap = types.shape[1]
    out = {}
    for a in np.flatnonzero(size > 0):
        at = (head[a] + np.arange(size[a])) % cap
        out[int(a)] = [(dep.row_of[a], int(types[a, j]))
                       + tuple(int(x) for x in payload[a, j]) for j in at]
    return out


def parsed(snap: dict, dep: Deployment):
    """(`queues_of`, `stashes_of`) of a reading, made once and kept in it
    (`_parsed`): the verdict reads every reading's queues and stashes more
    than once."""
    if "_parsed" not in snap:
        snap["_parsed"] = (queues_of(snap, dep), stashes_of(snap, dep))
    return snap["_parsed"]


def world_of(snap: dict, dep: Deployment, keys) -> World:
    """A reading as a World: where the reference takes over from the
    system."""
    (queues, _, rows), stashes = parsed(snap, dep)
    tellers = dict(snap["tellers"], key=np.asarray(keys, np.int64))
    return World({k: signed(snap["accounts"][k]) for k in ACCOUNT_COLUMNS},
                 stashes, tellers, queues, int(snap["steps"]), rows)


# ------------------------------------------------------------ the verdict
def _rows_wrong(have, want) -> int:
    n = min(len(have), len(want))
    return sum(1 for i in range(n) if have[i] != want[i]) \
        + abs(len(have) - len(want))


def compare(snap: dict, world: World, dep: Deployment, keys) -> dict:
    """Rows at which a reading and the reference's world differ: accounts,
    tellers, stashes (an account whose stash differs), every chip's queue
    row for row."""
    acc = snap["accounts"]
    wrong = np.zeros(dep.n_accounts, bool)
    for k in ACCOUNT_COLUMNS:
        wrong |= (np.asarray(acc[k], np.int64)
                  - np.asarray(world.accounts[k], np.int64)) % WRAP != 0
    tel = snap["tellers"]
    twrong = np.asarray(tel["key"]).astype(np.int64) % WRAP \
        != np.asarray(keys).astype(np.int64) % WRAP
    for k in TELLER_COLUMNS[1:]:
        twrong |= (np.asarray(tel[k], np.int64) - world.tellers[k]) % WRAP != 0
    (queues, malformed, _), have = parsed(snap, dep)
    stashes = sum(1 for a in set(have) | set(world.stash)
                  if have.get(a) != world.stash.get(a))
    return {"accounts_wrong": int(wrong.sum()),
            "tellers_wrong": int(twrong.sum()),
            "stashes_wrong": stashes,
            "queue_wrong": malformed + sum(
                _rows_wrong(h, w) for h, w in zip(queues, world.queues))}


def counters_wrong(have: dict, want: dict) -> dict:
    """|difference| of every counter (sums modulo 2^32), charged to
    `stashes_wrong` (the stash's two) or `queue_wrong` (the others)."""
    out = {"stashes_wrong": 0, "queue_wrong": 0}
    for k in COUNTERS:
        d = (int(have[k]) - int(want[k])) % WRAP
        d = min(d, WRAP - d)
        out["stashes_wrong" if k in STASH_COUNTERS else "queue_wrong"] += d
    return out


def expect(keys, dep: Deployment, got: dict) -> dict:
    """The replays `judge` compares with (`replay_fast`): from the seed to
    the window's opening, and from what the window left one chunk on; each
    a World and the cumulative counters it gives."""
    opened = World.start(keys, dep)
    at_open = replay_fast(opened, dep, int(got["open"]["steps"]))
    after = world_of(got["close"], dep, keys)
    chunk = replay_fast(after, dep, int(got["after"]["steps"]) - after.t)
    return {"open": opened, "open_counters": at_open,
            "after": after,
            "after_counters": advance(got["close"]["counters"], chunk)}


def ledger(snap: dict, dep: Deployment) -> int:
    """Balances and the Debits in flight (in the inbox, in a stash), summed
    modulo 2^32: 0 where no money was made or lost."""
    total = int(signed(snap["accounts"]["balance"]).sum())
    (queues, _, _), stashes = parsed(snap, dep)
    for queue in queues:
        total += sum(m[2] for m in queue if m[1] == DEBIT)
    for msgs in stashes.values():
        total += sum(m[2] for m in msgs if m[1] == DEBIT)
    return total % WRAP


def unaccounted(snap: dict, dep: Deployment) -> int:
    """|told - handled - waiting - lost| of one reading, as one_chip's
    `unaccounted` with what the counters own up to losing (the exchange,
    the spill regions, the stashes); and every spill region or pair chunk
    fuller than its high water says it ever was, by what it holds over."""
    acc = {k: signed(v) for k, v in snap["accounts"].items()}
    tel = {k: signed(v) for k, v in snap["tellers"].items()}
    c = snap["counters"]
    answered = int((acc["credits"] + acc["debits"] + acc["replies"]).sum())
    credits_told = int((tel["k"] + (int(snap["steps"]) > 0)).sum())
    handled = answered + int(acc["violations"].sum()) \
        + int((tel["done"] + tel["mismatch"]).sum())
    waiting = int(np.asarray(snap["inbox_valid"], bool).sum()) \
        + int(np.asarray(snap["stash"]["stash_size"], np.int64).sum())
    lost = sum(int(c[k]) for k in ("mail_dropped", "exchange_dropped",
                                   "stash_overflow"))
    over = 0
    for layout in parsed(snap, dep)[0][2]:
        over += max(0, layout[0] - int(c["spill_high_water"]))
        over += sum(max(0, n - int(c["exchange_high_water"]))
                    for n in layout[1:])
    return abs(credits_told + answered - handled - waiting - lost) + over


def judge(keys, dep: Deployment, got: dict, limits: dict,
          expected=None) -> dict:
    """Compare what the timed path left behind with the reference.

    `got`: three readings of the system, `open` (after the warm chunks),
    `close` (what the window left) and `after` (one more chunk through the
    same executable), each with `accounts` and `stash` by account id,
    `tellers` by index, the inbox as the system holds it (a block a chip),
    `steps`, and `counters` (COUNTERS, as the system reads them over its
    chips).

    1. From the seed: `accounts_wrong_at_open`, `tellers_wrong_at_open`,
       `stashes_wrong_at_open`, `queue_wrong_at_open`, the counters the
       replay counted on the way against the system's among them.
    2. From what the window left, one chunk on: `accounts_wrong`,
       `tellers_wrong`, `stashes_wrong`, `queue_wrong`, each counter's
       growth over the chunk against the replay's among them.
    3. Across the window, at the three readings: `ledger_wrong`,
       `messages_unaccounted`; after the last chunk: `teller_mismatch`,
       `protocol_violations`, `stash_overflow`, `messages_dropped` (what
       the exchange and the spill regions lost) and `exchange_dropped`."""
    expected = expected or expect(keys, dep, got)
    numbers = {}
    for suffix, when in (("_at_open", "open"), ("", "after")):
        found = compare(got[when], expected[when], dep, keys)
        for k, v in counters_wrong(got[when]["counters"],
                                   expected[when + "_counters"]).items():
            found[k] += v
        for k, v in found.items():
            numbers[k + suffix] = v
    readings = [got[w] for w in ("open", "close", "after")]
    last = got["after"]
    numbers.update(
        ledger_wrong=sum(1 for s in readings if ledger(s, dep) != 0),
        messages_unaccounted=sum(unaccounted(s, dep) for s in readings),
        teller_mismatch=int(signed(last["tellers"]["mismatch"]).sum()),
        protocol_violations=int(signed(
            last["accounts"]["violations"]).sum()),
        stash_overflow=int(last["counters"]["stash_overflow"]),
        messages_dropped=int(last["counters"]["mail_dropped"])
        + int(last["counters"]["exchange_dropped"]),
        exchange_dropped=int(last["counters"]["exchange_dropped"]))
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
