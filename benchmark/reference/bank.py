"""Plain reference for the bank: numpy only, nothing of the program.

Semantics (the Akka documentation's bank-account entity: Deposit, Withdraw
rejected on insufficient balance; a mailbox is a FIFO queue per
sender-receiver pair, dequeued one envelope at a time). A accounts in rows
[0, A), T tellers in rows [A, A + T). Teller i holds `home`, an odd `stride`,
a `mask` of P = `period` bits and `c` below L = `levels`. At step t, the
system's own step counter at the start of the step (0 for the first), with
p = t mod P, it tells account

    (home_i + stride_i * p) mod A

a Withdraw if bit p of mask_i is set, else a Deposit, of amount

    1 + ((c_i + t) mod L)  for a Deposit,   2 + ((c_i + t) mod L)  for a Withdraw.

An account holds `balance`, `deposited`, `withdrawn`, `rejected`, `applied`,
`last_teller`, all 0 at the start, and applies ONE COMMAND AT A TIME:

    Deposit(a):   balance += a, deposited += a
    Withdraw(a):  if balance >= a: balance -= a, withdrawn += a
                  else: rejected += 1
    every command: applied += 1, last_teller = the sender's row

A command told at step t waits in the queue and is applied at step t + 1 or
later. At a step an account takes the commands waiting for it IN QUEUE
ORDER, what it left over at earlier steps first (in the order it had), then
the last step's fresh mail by the sender's row; it applies the first S =
`mailbox_slots` of them and leaves the rest over. Nothing is ever dropped.
The queue as a whole is therefore: the leftovers, by account and within an
account in order, then the fresh mail by teller row; so the system's inbox
holds it (spill region first) and `judge` compares it row for row.

`step` is that rule, literal: vectorised across accounts only, the j-th
command of every mailbox that has one applied together, j = 0 .. S-1 one
after another; never across one mailbox's commands. The fold has no closed
form over a period, so `judge` replays: from the seed up to where the window
opens, and one chunk on from what the window left; across the window it
holds the system to its own counters. Totals are int32 in the system and
wrap: they are compared modulo 2^32."""

from __future__ import annotations

import numpy as np

ACCOUNT_COLUMNS = ("balance", "deposited", "withdrawn", "rejected",
                   "applied", "last_teller")
TELLER_COLUMNS = ("home", "stride", "mask", "c")
QUEUE_COLUMNS = ("account", "kind", "amount", "teller")
DEPOSIT, WITHDRAW = 1, 2
WIDTH = 4
WRAP = 1 << 32


def seed_tellers(n_tellers: int, n_accounts: int, seed: int,
                 traffic: dict) -> dict:
    """The deployment's tellers, from the seed: `home` uniform over the
    accounts, `stride` odd and below their number (so a teller visits
    `period` distinct accounts where that number is a power of two), each of
    the mask's `period` bits set with probability 1/2, `c` below `levels`."""
    rng = np.random.default_rng([int(seed), 0x42414E4B])
    return {"home": rng.integers(0, n_accounts, n_tellers),
            "stride": 2 * rng.integers(0, max(n_accounts // 2, 1),
                                       n_tellers) + 1,
            "mask": rng.integers(0, 1 << int(traffic["period"]), n_tellers),
            "c": rng.integers(0, int(traffic["levels"]), n_tellers)}


def emissions(t: int, tellers: dict, n_accounts: int, traffic: dict) -> dict:
    """What the tellers tell at step t, by teller row."""
    p = t % int(traffic["period"])
    withdraw = (tellers["mask"] >> p) & 1
    return {"account": (tellers["home"] + tellers["stride"] * p) % n_accounts,
            "kind": np.where(withdraw == 1, WITHDRAW, DEPOSIT),
            "amount": 1 + withdraw + (tellers["c"] + t) % int(
                traffic["levels"]),
            "teller": n_accounts + np.arange(tellers["home"].shape[0])}


def zero_accounts(n_accounts: int) -> dict:
    return {k: np.zeros(n_accounts, np.int64) for k in ACCOUNT_COLUMNS}


def empty_queue() -> dict:
    return {k: np.zeros(0, np.int64) for k in QUEUE_COLUMNS}


def take(queue: dict, rows) -> dict:
    return {k: v[rows] for k, v in queue.items()}


def join(first: dict, then: dict) -> dict:
    return {k: np.concatenate([first[k], then[k]]) for k in QUEUE_COLUMNS}


def mailboxes(queue: dict):
    """The queue's commands by account, each account's in queue order, and
    every command's place in its account's mailbox (0 for the oldest)."""
    order = np.argsort(queue["account"], kind="stable")
    ordered = take(queue, order)
    account = ordered["account"]
    place = np.arange(account.shape[0]) \
        - np.searchsorted(account, account, side="left")
    return ordered, place


def apply(accounts: dict, commands: dict) -> None:
    """One command for each of DISTINCT accounts, by the literal rule."""
    who, amount = commands["account"], commands["amount"]
    deposit = commands["kind"] == DEPOSIT
    enough = accounts["balance"][who] >= amount
    accounts["balance"][who] += np.where(
        deposit, amount, np.where(enough, -amount, 0))
    accounts["deposited"][who] += np.where(deposit, amount, 0)
    accounts["withdrawn"][who] += np.where(~deposit & enough, amount, 0)
    accounts["rejected"][who] += ~deposit & ~enough
    accounts["applied"][who] += 1
    accounts["last_teller"][who] = commands["teller"]


def consume(accounts: dict, ordered: dict, place, slots: int) -> dict:
    """Every mailbox's first `slots` commands, one at a time: the j-th of
    every mailbox that has one, then the (j+1)-th."""
    accounts = {k: v.copy() for k, v in accounts.items()}
    for j in range(slots):
        rows = np.flatnonzero(place == j)
        if rows.size == 0:
            break
        apply(accounts, take(ordered, rows))
    return accounts


def step(accounts: dict, queue: dict, t: int, tellers: dict, traffic: dict,
         slots: int):
    """One literal step. Returns (accounts, queue, carried): the accounts
    after their mailboxes' first `slots` commands, the queue the next step
    finds (what was left over, then step t's tells by teller row) and how
    many commands were left over."""
    n_accounts = accounts["balance"].shape[0]
    ordered, place = mailboxes(queue)
    accounts = consume(accounts, ordered, place, slots)
    left = take(ordered, place >= slots)
    fresh = emissions(t, tellers, n_accounts, traffic)
    return accounts, join(left, fresh), int(left["account"].shape[0])


def replay(accounts: dict, queue: dict, t0: int, steps: int, tellers: dict,
           traffic: dict, slots: int, step_fn=step):
    """`steps` steps from step t0 on. Returns (accounts, queue, carried):
    `carried` the commands left over at each of the steps."""
    carried = []
    for t in range(t0, t0 + steps):
        accounts, queue, left = step_fn(accounts, queue, t, tellers, traffic,
                                        slots)
        carried.append(left)
    return accounts, queue, np.asarray(carried, np.int64)


# ------------------------------------------------------------ the verdict
def queue_of(snap: dict, n_accounts: int):
    """The commands a system's inbox holds, in row order, as a queue, and
    how many of its valid rows are no command of this deployment (another
    address than an account, another tag than Deposit or Withdraw, a
    payload that is not `[amount, a row, 0, 0]` in whole numbers): those
    are left out of the queue and counted."""
    valid = np.asarray(snap["inbox_valid"], bool)
    dst = np.asarray(snap["inbox_dst"], np.int64)[valid]
    kind = np.asarray(snap["inbox_type"], np.int64)[valid]
    payload = np.asarray(snap["inbox_payload"], np.float64).reshape(
        -1, WIDTH)[valid]
    whole = np.where(np.isfinite(payload), payload, -1).astype(np.int64)
    fine = (dst >= 0) & (dst < n_accounts) \
        & ((kind == DEPOSIT) | (kind == WITHDRAW)) \
        & (whole == payload).all(axis=1) & (whole[:, 0] >= 1) \
        & (whole[:, 1] >= 0) & (whole[:, 2:] == 0).all(axis=1)
    queue = {"account": dst[fine], "kind": kind[fine],
             "amount": whole[fine, 0], "teller": whole[fine, 1]}
    return queue, int((~fine).sum())


def queue_wrong(have: dict, malformed: int, want: dict) -> int:
    """Rows at which two queues differ, compared in order, with the rows
    one has and the other lacks, and the malformed ones."""
    n = min(have["account"].shape[0], want["account"].shape[0])
    differ = np.zeros(n, bool)
    for k in QUEUE_COLUMNS:
        differ |= have[k][:n] != want[k][:n]
    return int(differ.sum()) + malformed + abs(
        have["account"].shape[0] - want["account"].shape[0])


def accounts_wrong(have: dict, want: dict) -> int:
    wrong = np.zeros(want["balance"].shape[0], bool)
    for k in ACCOUNT_COLUMNS:
        wrong |= (np.asarray(have[k], np.int64) - want[k]) % WRAP != 0
    return int(wrong.sum())


def signed(column) -> np.ndarray:
    """An int32 column that may have wrapped, as the system reads it."""
    return (np.asarray(column, np.int64) + (1 << 31)) % WRAP - (1 << 31)


def _held(snap: dict) -> int:
    """The commands a reading accounts for: applied, or waiting."""
    return int(signed(snap["accounts"]["applied"]).sum()) \
        + int(np.asarray(snap["inbox_valid"], bool).sum())


def expect(tellers: dict, traffic: dict, n_accounts: int, slots: int,
           got: dict) -> dict:
    """What `judge` compares `got` with: the replay from the seed up to the
    window's opening, and the replay of the steps after the window from
    what the window left (`got["close"]`)."""
    at_open = replay(zero_accounts(n_accounts), empty_queue(), 0,
                     int(got["open"]["steps"]), tellers, traffic, slots)
    close = got["close"]
    left = {k: signed(close["accounts"][k]) for k in ACCOUNT_COLUMNS}
    queue, _ = queue_of(close, n_accounts)
    after = replay(left, queue, int(close["steps"]),
                   int(got["after"]["steps"]) - int(close["steps"]),
                   tellers, traffic, slots)
    return {"open": at_open, "after": after}


def judge(tellers: dict, traffic: dict, n_accounts: int, slots: int,
          got: dict, limits: dict, expected=None) -> dict:
    """Compare what the timed path left behind with the reference.

    `got`: three readings of the system, `open` (after the warm chunks,
    before the window), `close` (what the window left) and `after` (one more
    chunk through the same executable), each with `accounts` (column ->
    array), `inbox_dst` / `inbox_type` / `inbox_payload` / `inbox_valid` as
    the system holds them (the spill region first) and `steps`, the system's
    own step counter; `after` also with `tellers`; and `dropped`, the
    device's and the host's drop counters summed. `expected`: what `expect`
    returns for these arguments, for a caller that judges several outcomes
    of one run.

    1. From the seed: `accounts_wrong_at_open`, `queue_wrong_at_open`.
    2. The transition, from what the window left: `accounts_wrong`,
       `queue_wrong` (the rows the inbox holds, in order), `tellers_wrong`.
    3. Across the window, from the system's own counters: per account
       `balance == deposited - withdrawn` (`ledger_wrong`) and `balance >=
       0` (`negative_balances`), at the window's end and a chunk later; the
       commands sent between two readings (steps x tellers) against the
       growth of `applied` plus the growth of the queue
       (`messages_unaccounted`), over the window and over the chunk after
       it; `messages_dropped`."""
    expected = expected or expect(tellers, traffic, n_accounts, slots, got)
    n_tellers = tellers["home"].shape[0]
    numbers = {}
    for name, when in (("_at_open", "open"), ("", "after")):
        accounts, queue, _ = expected[when]
        have, malformed = queue_of(got[when], n_accounts)
        numbers["accounts_wrong" + name] = accounts_wrong(
            got[when]["accounts"], accounts)
        numbers["queue_wrong" + name] = queue_wrong(have, malformed, queue)
    _, malformed = queue_of(got["close"], n_accounts)
    numbers["queue_wrong"] += malformed  # the replay left those out
    changed = np.zeros(n_tellers, bool)
    for k in TELLER_COLUMNS:
        changed |= np.asarray(got["after"]["tellers"][k],
                              np.int64) != tellers[k]
    numbers["tellers_wrong"] = int(changed.sum())

    ledger = negative = unaccounted = 0
    readings = [got[when] for when in ("open", "close", "after")]
    for snap in readings[1:]:
        acc = {k: signed(snap["accounts"][k]) for k in ACCOUNT_COLUMNS}
        ledger += int(((acc["balance"] - acc["deposited"] + acc["withdrawn"])
                       % WRAP != 0).sum())
        negative += int((acc["balance"] < 0).sum())
    for a, b in zip(readings, readings[1:]):
        sent = (int(b["steps"]) - int(a["steps"])) * n_tellers
        unaccounted += abs(sent - (_held(b) - _held(a)))
    numbers.update(ledger_wrong=ledger, negative_balances=negative,
                   messages_unaccounted=unaccounted,
                   messages_dropped=int(got["dropped"]))
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
