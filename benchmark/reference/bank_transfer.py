"""Plain reference for Savina's Bank Transaction: numpy and Python only,
nothing of the program.

Semantics (Savina, "Bank Transaction", Akka variant with a stash; here with
Akka typed's `unstashAll` and closed-loop tellers). A accounts in rows
[0, A), T tellers in rows [A, A + T). Teller i holds a 32-bit key and k, the
index of its outstanding transfer. Transfer k of key is

    u_j = fmix32(key ^ fmix32(k * 0x9E3779B1 + j)),  j = 1, 2, 3  (uint32)
    src = u_1 mod S,  dst = src + max(1, u_2 mod (A - src)),
    amount = 1 + u_3 mod 1000,

with S = (A div 10) * 8 and fmix32 MurmurHash3's finalizer. At step 0, and
at every step at which its Reply is delivered, a teller tells the next
transfer's Credit[amount, dst, its own row, k] to src (k counts up from 0; a
Reply that carries another k, or two Replies at once, is a `mismatch`).

An account holds `balance`, `in_reply`, `teller`, `tk`, `credits`, `debits`,
`replies`, `violations` (all 0 at the start) and a stash, and handles ONE
MESSAGE AT A TIME:

    Credit[amount, dst, teller, k]: balance -= amount; tell Debit[amount,
        its own row] to dst; remember teller and k; enter reply mode
    Debit[amount, src]: balance += amount; tell Reply to src
    Reply: in reply mode, tell Reply[tk] to the remembered teller and leave
        it; out of it, violations += 1 and nothing is told
    in reply mode every message but a Reply is stashed (the oldest first);
    out of it, the stash's oldest message is handled before any mail.

The step's schedule: a message told at step t is in the queue of step t + 1.
An account's queue is what it left at earlier steps (in order), then the
fresh mail by sender row, each sender's in the order it told it; it may take
the first M = `mailbox_slots` of it. Handled messages (the stash's and the
mail's) each tell one message, and an account tells at most B =
`emit_width` a step: the first message that would pass B ends its step, and
it and the rest of the account's queue are left, in order, ahead of the next
step's mail. A stash holds at most C = `stash_capacity` messages (more is
lost, and counted); what is left over ahead of the next step holds at most
`spill_capacity` (the first, by account; more is lost). The queue as a
whole is therefore: the left-over mail by account, then the step's tells by
sender row, accounts first; so the system's inbox holds it (spill region
first) and `judge` compares it row for row.

`step` is that rule, literal: a Python loop over the accounts that have
something to do, one message at a time. The fold has no closed form, so
`judge` replays from the seed up to where the window opens and one chunk on
from what the window left, and holds the window to the system's own
counters. Totals are int32 in the system and wrap: compared modulo 2^32."""

from __future__ import annotations

import numpy as np

CREDIT, DEBIT, REPLY = 1, 2, 3
ACCOUNT_COLUMNS = ("balance", "in_reply", "teller", "tk", "credits",
                   "debits", "replies", "violations")
TELLER_COLUMNS = ("key", "k", "done", "mismatch")
WIDTH = 4
WRAP = 1 << 32
MASK = WRAP - 1


# ------------------------------------------------------------ the tellers
def seed_keys(n_tellers: int, seed: int) -> np.ndarray:
    """Every teller's key from the seed, as int32 bit patterns."""
    rng = np.random.default_rng([int(seed), 0x53415649])
    return rng.integers(0, WRAP, n_tellers, dtype=np.uint64).astype(
        np.uint32).view(np.int32)


def _fmix(h):
    h = h & MASK
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & MASK
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & MASK
    return h ^ (h >> 16)


def transfers(keys, ks, n_accounts: int, amounts: int = 1000):
    """(src, dst, amount) of transfer `ks` of tellers with `keys`, int64."""
    key = np.asarray(keys).astype(np.int64) & MASK
    base = (np.asarray(ks, np.int64) * 0x9E3779B1) & MASK
    u1, u2, u3 = (_fmix(key ^ _fmix(base + j)) for j in (1, 2, 3))
    sources = (n_accounts // 10) * 8
    src = u1 % sources
    dst = src + np.maximum(u2 % (n_accounts - src), 1)
    return src, dst, 1 + u3 % amounts


# -------------------------------------------------------------- the world
class World:
    """Everything the rule reads: accounts (Python lists, a column each),
    stashes (account -> list of messages, non-empty only), tellers (numpy,
    int64), the queue (a list of messages in order) and the step. A message
    is a tuple (dst, type, a0, a1, a2, a3) of ints."""

    def __init__(self, accounts, stash, tellers, queue, t):
        self.accounts = {k: [int(x) for x in np.asarray(accounts[k])]
                         for k in ACCOUNT_COLUMNS}
        self.stash = {a: list(s) for a, s in stash.items() if s}
        self.tellers = {k: np.asarray(tellers[k], np.int64).copy()
                        for k in TELLER_COLUMNS}
        self.queue = list(queue)
        self.t = int(t)

    @staticmethod
    def start(keys, n_accounts: int) -> "World":
        n = np.asarray(keys).shape[0]
        zero = np.zeros(n, np.int64)
        return World({k: np.zeros(n_accounts, np.int64)
                      for k in ACCOUNT_COLUMNS}, {},
                     {"key": np.asarray(keys, np.int64), "k": zero,
                      "done": zero, "mismatch": zero}, [], 0)


class Rule:
    """The deployment's sizes the schedule reads."""

    def __init__(self, n_accounts, slots, emit_width, stash_capacity,
                 spill_capacity):
        self.n_accounts, self.slots = int(n_accounts), int(slots)
        self.budget, self.cap = int(emit_width), int(stash_capacity)
        self.spill = int(spill_capacity)


def handle(acc, a, msg):
    """One message handled by account `a` (not stashed): its effect, and
    what it tells (a message, or None)."""
    _, kind, a0, a1, a2, a3 = msg
    if kind == CREDIT:
        acc["balance"][a] -= a0
        acc["teller"][a], acc["tk"][a] = a2, a3
        acc["in_reply"][a] = 1
        acc["credits"][a] += 1
        return (a1, DEBIT, a0, a, 0, 0)
    if kind == DEBIT:
        acc["balance"][a] += a0
        acc["debits"][a] += 1
        return (a1, REPLY, 0, 0, 0, 0)
    if acc["in_reply"][a]:
        acc["in_reply"][a] = 0
        acc["replies"][a] += 1
        return (acc["teller"][a], REPLY, acc["tk"][a], 0, 0, 0)
    acc["violations"][a] += 1
    return None


def account_turns(world, rule, a, box, out, counts, handle=handle,
                  budget=None):
    """Account `a`'s step: the stash first while out of reply mode, then
    the first `slots` of its queue `box`, under the budget. Appends what it
    tells to `out`; returns what it leaves of `box`."""
    acc, limit = world.accounts, rule.budget if budget is None else budget
    mail = box[:rule.slots]
    used = i = 0
    while True:
        stash = world.stash.get(a)
        if stash and not acc["in_reply"][a]:
            msg = stash[0]
            if used + 1 > limit:
                break
            stash.pop(0)
            counts["replayed"] = counts.get("replayed", 0) + 1
            if not stash:
                del world.stash[a]
        elif i < len(mail):
            msg = mail[i]
            if acc["in_reply"][a] and msg[1] != REPLY:
                stash = world.stash.setdefault(a, [])
                if len(stash) < rule.cap:
                    stash.append(msg)
                    counts["stashed"] += 1
                else:
                    counts["lost"] += 1
                i += 1
                continue
            tells = msg[1] != REPLY or acc["in_reply"][a]
            if used + tells > limit:
                break
            i += 1
        else:
            break
        told = handle(acc, a, msg)
        if told is not None:
            out.append(told)
            used += 1
    return mail[i:] + box[rule.slots:]


def step(world: World, rule: Rule, turns=account_turns) -> dict:
    """One literal step, in place. Returns the step's counts: `stashed`,
    `lost` (by a full stash), `replayed` (handled out of a stash),
    `retained` (mail the budget left), `left` (all mail left over, spill
    region), `spill_lost`."""
    A = rule.n_accounts
    boxes, replies = {}, {}
    for msg in world.queue:
        (boxes if msg[0] < A else replies).setdefault(msg[0], []).append(msg)
    busy = set(boxes) | {a for a in world.stash
                         if not world.accounts["in_reply"][a]}
    counts = {"stashed": 0, "lost": 0, "retained": 0, "replayed": 0}
    left, told = [], []
    for a in sorted(busy):
        box = boxes.get(a, [])
        rest = turns(world, rule, a, box, told, counts)
        counts["retained"] += len(rest) - max(len(box) - rule.slots, 0)
        left.extend(rest)
    # the tellers, by row: a Reply delivered, then the next transfer
    tel = world.tellers
    n = tel["k"].shape[0]
    got = np.zeros(n, np.int64)
    said = np.zeros(n, np.int64)
    for row, msgs in replies.items():
        got[row - A] = len(msgs)
        said[row - A] = sum(m[2] for m in msgs)
    heard = got > 0
    right = (got == 1) & (said == tel["k"])
    tel["done"] += heard & right
    tel["mismatch"] += heard & ~right
    tel["k"] += heard
    issue = np.flatnonzero(heard | (world.t == 0))
    src, dst, amount = transfers(tel["key"][issue], tel["k"][issue], A)
    credits = [(int(s), CREDIT, int(m), int(d), A + int(i), int(k))
               for s, d, m, i, k in zip(src, dst, amount, issue,
                                        tel["k"][issue])]
    counts["left"] = len(left)
    counts["spill_lost"] = max(len(left) - rule.spill, 0)
    world.queue = left[:rule.spill] + told + credits
    world.t += 1
    return counts


def replay(world: World, rule: Rule, steps: int, turns=account_turns):
    """`steps` steps on; returns the summed counts and the high waters."""
    total = {"stashed": 0, "lost": 0, "replayed": 0, "retained": 0,
             "left": 0, "spill_lost": 0, "left_high": 0, "stash_high": 0}
    for _ in range(steps):
        c = step(world, rule, turns)
        for k in ("stashed", "lost", "replayed", "retained", "left",
                  "spill_lost"):
            total[k] += c[k]
        total["left_high"] = max(total["left_high"], c["left"])
        total["stash_high"] = max([total["stash_high"]]
                                  + [len(s) for s in world.stash.values()])
    return total


# ------------------------------------------------------------ a reading
def queue_of(snap: dict):
    """The messages a system's inbox holds, in row order, and how many of
    its valid rows are none of this deployment's (a tag other than Credit,
    Debit or Reply, a payload not in whole numbers)."""
    valid = np.asarray(snap["inbox_valid"], bool)
    dst = np.asarray(snap["inbox_dst"], np.int64)[valid]
    kind = np.asarray(snap["inbox_type"], np.int64)[valid]
    payload = np.asarray(snap["inbox_payload"], np.float64).reshape(
        -1, WIDTH)[valid]
    whole = np.where(np.isfinite(payload), payload, -1).astype(np.int64)
    fine = (dst >= 0) & np.isin(kind, (CREDIT, DEBIT, REPLY)) \
        & (whole == payload).all(axis=1)
    rows = np.concatenate([dst[:, None], kind[:, None], whole], axis=1)[fine]
    return [tuple(int(x) for x in r) for r in rows], int((~fine).sum())


def stashes_of(snap: dict) -> dict:
    """account -> its stash as messages, oldest first (non-empty only): a
    ring of C entries, the oldest at `stash_head`."""
    st = snap["stash"]
    size = np.asarray(st["stash_size"], np.int64)
    head = np.asarray(st["stash_head"], np.int64)
    types = np.asarray(st["stash_type"], np.int64)
    payload = np.asarray(st["stash_payload"], np.float64)
    cap = types.shape[1]
    out = {}
    for a in np.flatnonzero(size > 0):
        at = (head[a] + np.arange(size[a])) % cap
        out[int(a)] = [(int(a), int(types[a, j]))
                       + tuple(int(x) for x in payload[a, j]) for j in at]
    return out


def world_of(snap: dict, keys) -> World:
    """A reading as a World: where the reference takes over from the
    system."""
    queue, _ = queue_of(snap)
    tellers = dict(snap["tellers"], key=np.asarray(keys, np.int64))
    return World({k: signed(snap["accounts"][k]) for k in ACCOUNT_COLUMNS},
                 stashes_of(snap), tellers, queue, int(snap["steps"]))


def signed(column) -> np.ndarray:
    """An int32 column that may have wrapped, as the system reads it."""
    return (np.asarray(column, np.int64) + (1 << 31)) % WRAP - (1 << 31)


# ------------------------------------------------------------ the verdict
def _rows_wrong(have, want) -> int:
    n = min(len(have), len(want))
    return sum(1 for i in range(n) if have[i] != want[i]) \
        + abs(len(have) - len(want))


def compare(snap: dict, world: World, keys) -> dict:
    """Rows at which a reading and the reference's world differ: accounts,
    tellers, stashes (an account whose stash differs), the queue."""
    acc = snap["accounts"]
    wrong = np.zeros(len(world.accounts["balance"]), bool)
    for k in ACCOUNT_COLUMNS:
        wrong |= (np.asarray(acc[k], np.int64)
                  - np.asarray(world.accounts[k], np.int64)) % WRAP != 0
    tel = snap["tellers"]
    twrong = np.asarray(tel["key"]).astype(np.int64) & MASK \
        != np.asarray(keys).astype(np.int64) & MASK
    for k in TELLER_COLUMNS[1:]:
        twrong |= (np.asarray(tel[k], np.int64) - world.tellers[k]) % WRAP != 0
    have = stashes_of(snap)
    stashes = sum(1 for a in set(have) | set(world.stash)
                  if have.get(a) != world.stash.get(a))
    queue, malformed = queue_of(snap)
    return {"accounts_wrong": int(wrong.sum()),
            "tellers_wrong": int(twrong.sum()),
            "stashes_wrong": stashes,
            "queue_wrong": _rows_wrong(queue, world.queue) + malformed}


def expect(keys, rule: Rule, got: dict) -> dict:
    """The replays `judge` compares with: from the seed to the window's
    opening, and from what the window left one chunk on."""
    opened = World.start(keys, rule.n_accounts)
    replay(opened, rule, int(got["open"]["steps"]))
    after = world_of(got["close"], keys)
    replay(after, rule, int(got["after"]["steps"]) - after.t)
    return {"open": opened, "after": after}


def ledger(snap: dict) -> int:
    """Balances and the Debits in flight (in the inbox, in a stash), summed
    modulo 2^32: 0 where no money was made or lost."""
    total = int(signed(snap["accounts"]["balance"]).sum())
    queue, _ = queue_of(snap)
    total += sum(m[2] for m in queue if m[1] == DEBIT)
    for msgs in stashes_of(snap).values():
        total += sum(m[2] for m in msgs if m[1] == DEBIT)
    return total % WRAP


def unaccounted(snap: dict) -> int:
    """|told - handled - waiting| of one reading: what the tellers and the
    accounts told, against what was handled and what waits in the inbox
    (spill region included) or a stash."""
    acc = {k: signed(v) for k, v in snap["accounts"].items()}
    tel = {k: signed(v) for k, v in snap["tellers"].items()}
    answered = int((acc["credits"] + acc["debits"] + acc["replies"]).sum())
    issued = int((tel["k"] + (int(snap["steps"]) > 0)).sum())
    handled = answered + int(acc["violations"].sum()) \
        + int((tel["done"] + tel["mismatch"]).sum())
    waiting = int(np.asarray(snap["inbox_valid"], bool).sum()) \
        + int(np.asarray(snap["stash"]["stash_size"], np.int64).sum())
    return abs(issued + answered - handled - waiting)


def judge(keys, rule: Rule, got: dict, limits: dict, expected=None) -> dict:
    """Compare what the timed path left behind with the reference.

    `got`: three readings of the system, `open` (after the warm chunks),
    `close` (what the window left) and `after` (one more chunk through the
    same executable), each with `accounts`, `stash` (`stash_type`,
    `stash_payload`, `stash_size` of the accounts), `tellers`, the inbox
    (`inbox_dst` / `_type` / `_payload` / `_valid`, spill region first) and
    `steps`; `dropped`, the device's and the host's drop counters summed;
    `counters`, the system's `read_emit_stats()` after the last chunk.

    1. From the seed: `accounts_wrong_at_open`, `tellers_wrong_at_open`,
       `stashes_wrong_at_open`, `queue_wrong_at_open`.
    2. From what the window left, one chunk on: `accounts_wrong`,
       `tellers_wrong`, `stashes_wrong`, `queue_wrong`.
    3. Across the window, from the system's own columns and counters, at
       the three readings: `ledger_wrong` (readings at which money was made
       or lost), `messages_unaccounted`; after the last chunk:
       `teller_mismatch`, `protocol_violations`, `stash_overflow`,
       `messages_dropped`."""
    expected = expected or expect(keys, rule, got)
    numbers = {}
    for suffix, when in (("_at_open", "open"), ("", "after")):
        for k, v in compare(got[when], expected[when], keys).items():
            numbers[k + suffix] = v
    readings = [got[w] for w in ("open", "close", "after")]
    last = got["after"]
    numbers.update(
        ledger_wrong=sum(1 for s in readings if ledger(s) != 0),
        messages_unaccounted=sum(unaccounted(s) for s in readings),
        teller_mismatch=int(signed(last["tellers"]["mismatch"]).sum()),
        protocol_violations=int(signed(
            last["accounts"]["violations"]).sum()),
        stash_overflow=int(got["counters"].get("stash_overflow", 0)),
        messages_dropped=int(got["dropped"]))
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
