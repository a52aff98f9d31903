"""The bank's controls: the reference put in the program's place with ONE of
the configuration's guarantees broken. Each has to come out as not correct,
by the limits named for it here (`CAUGHT_BY`) and no other, or the comparison
that decides `correct` proves nothing. The configuration states no numeric
precision: every number compared is exact, with the limit 0.

A control stands where the program stood for the chunk after the window: it
starts from what the window left (`got["close"]`, on the chip the program's
own state after a real window) and runs that chunk's steps by the reference's
rule with one stage of it broken (`step_with`), or alters a reading the way
the fault it names would. Each checks that it did change what it set out to
change, and raises where the run gives it nothing to break (a run in which
no mailbox overflows has no spill to lose).

`benchmark/tools/control_bank.py` runs them on the chip at the cell's own
size; tests/benchmark runs them at toy sizes."""

from __future__ import annotations

import numpy as np

from benchmark.reference import bank as ref


class NothingToBreak(ValueError):
    """The run holds no place where this control's fault would show."""


# ------------------------------------------------- one stage of a step broken
def _order_matters(accounts, ordered, place, slots):
    """A row j such that commands j and j + 1 are neighbours in one mailbox,
    both consumed at this step, and leave another balance and another count
    of rejections applied the other way round: a Deposit, then a Withdraw
    that only the Deposit makes affordable. The balance each command finds
    is replayed, so the search is exact. None where the step has no such
    pair."""
    scratch = {k: v.copy() for k, v in accounts.items()}
    m = place.shape[0]
    for j in range(slots - 1):
        rows = np.flatnonzero(place == j)
        if rows.size == 0:
            break
        pairs = rows[rows + 1 < m]
        pairs = pairs[place[pairs + 1] == j + 1]
        now = scratch["balance"][ordered["account"][pairs]]
        first, then = ordered["amount"][pairs], ordered["amount"][pairs + 1]
        matters = (ordered["kind"][pairs] == ref.DEPOSIT) \
            & (ordered["kind"][pairs + 1] == ref.WITHDRAW) \
            & (now < then) & (now + first >= then)
        if matters.any():
            return int(pairs[np.flatnonzero(matters)[0]])
        ref.apply(scratch, ref.take(ordered, rows))
    return None


def _consume_commutatively(accounts, ordered, place, slots):
    """What reduce delivery would compute: a mailbox's first `slots`
    commands as sums, the balance check skipped."""
    accounts = {k: v.copy() for k, v in accounts.items()}
    rows = np.flatnonzero(place < slots)
    who = ordered["account"][rows]
    amount = ordered["amount"][rows]
    deposit = ordered["kind"][rows] == ref.DEPOSIT
    np.add.at(accounts["deposited"], who, np.where(deposit, amount, 0))
    np.add.at(accounts["withdrawn"], who, np.where(deposit, 0, amount))
    np.add.at(accounts["balance"], who, np.where(deposit, amount, -amount))
    np.add.at(accounts["applied"], who, 1)
    last = rows[np.append(who[1:] != who[:-1], True)] if rows.size else rows
    accounts["last_teller"][ordered["account"][last]] = \
        ordered["teller"][last]
    return accounts


def step_with(fault: str, state: dict):
    """A `step` of the reference with one stage broken. `state` remembers
    whether the fault has been planted (`done`): the faults of one command
    are planted once, at the first step that offers a place; the faults of
    the rule at every step."""

    def step(accounts, queue, t, tellers, traffic, slots):
        n_accounts = accounts["balance"].shape[0]
        carried = int(queue.get("carried", 0))
        queue = {k: queue[k] for k in ref.QUEUE_COLUMNS}
        if fault == "spill_behind_fresh" and carried:
            # redelivered mail behind the fresh mail, not ahead of it
            m = queue["account"].shape[0]
            rows = np.concatenate([np.arange(carried, m), np.arange(carried)])
            queue = ref.take(queue, rows)
            state["done"] = True
        ordered, place = ref.mailboxes(queue)
        if fault == "neighbours_swapped" and not state.get("done"):
            j = _order_matters(accounts, ordered, place, slots)
            if j is not None:
                rows = np.arange(place.shape[0])
                rows[[j, j + 1]] = rows[[j + 1, j]]
                ordered = ref.take(ordered, rows)
                state["done"] = True
        take_slots = slots
        if fault == "folded_commutatively":
            accounts = _consume_commutatively(accounts, ordered, place, slots)
            state["done"] = True
        else:
            accounts = ref.consume(accounts, ordered, place, slots)
        over = np.flatnonzero(place == slots)  # the first command left over
        if fault == "slot_cap_ignored" and over.size:
            # one more than the mailbox's slots, in every full mailbox
            ref.apply(accounts, ref.take(ordered, over))
            take_slots = slots + 1
            state["done"] = True
        if fault == "applied_twice" and not state.get("done") \
                and place.shape[0]:
            ref.apply(accounts, ref.take(ordered, np.asarray([0])))
            state["done"] = True
        keep = place >= take_slots
        if fault == "spill_lost" and not state.get("done") and over.size:
            keep[over[0]] = False
            state["done"] = True
        left = ref.take(ordered, keep)
        fresh = ref.emissions(t, tellers, n_accounts, traffic)
        out = ref.join(left, fresh)
        out["carried"] = int(left["account"].shape[0])
        return accounts, out, out["carried"]

    return step


STEP_FAULTS = ("neighbours_swapped", "folded_commutatively",
               "spill_behind_fresh", "spill_lost", "applied_twice",
               "slot_cap_ignored")


# ------------------------------------------------------ a reading altered
def _inbox_of(queue: dict) -> dict:
    """A queue as the rows of an inbox."""
    m = queue["account"].shape[0]
    payload = np.zeros((m, ref.WIDTH), np.float32)
    payload[:, 0], payload[:, 1] = queue["amount"], queue["teller"]
    return {"inbox_dst": queue["account"].astype(np.int64),
            "inbox_type": queue["kind"].astype(np.int64),
            "inbox_payload": payload, "inbox_valid": np.ones(m, bool)}


def _reading(accounts, queue, steps, tellers=None) -> dict:
    snap = {"accounts": {k: v.copy() for k, v in accounts.items()},
            "steps": int(steps), **_inbox_of(queue)}
    if tellers is not None:
        snap["tellers"] = {k: tellers[k].copy() for k in ref.TELLER_COLUMNS}
    return snap


def _richest(snap) -> int:
    return int(np.argmax(ref.signed(snap["accounts"]["balance"])))


def withdraw_unchecked(got, case):
    """One Withdraw larger than the balance, applied all the same."""
    acc = got["after"]["accounts"]
    i = _richest(got["after"])
    more = int(ref.signed(acc["balance"])[i]) + 1
    acc["balance"][i] -= more
    acc["withdrawn"][i] += more


def deposit_not_booked(got, case):
    """A Deposit that moved the balance and not the ledger."""
    got["after"]["accounts"]["balance"][_richest(got["after"])] += 1


def drop_counted(got, case):
    got["dropped"] = 1


def teller_rewired(got, case):
    home = got["after"]["tellers"]["home"]
    home[home.shape[0] // 3] = (home[home.shape[0] // 3] + 1) % case.n_accounts


def open_account_altered(got, case):
    """A warm chunk that left one account another balance, ledger and all."""
    acc = got["open"]["accounts"]
    acc["balance"][0] += 1
    acc["deposited"][0] += 1


def open_queue_reordered(got, case):
    """Two waiting commands of different senders changed places before the
    window opened."""
    snap = got["open"]
    rows = np.flatnonzero(snap["inbox_valid"])
    if rows.size < 2:
        raise NothingToBreak("fewer than two commands wait at the opening")
    a, b = rows[0], rows[-1]
    for k in ("inbox_dst", "inbox_type", "inbox_payload"):
        snap[k][[a, b]] = snap[k][[b, a]]


READING_FAULTS = {f.__name__: f for f in (
    withdraw_unchecked, deposit_not_booked, drop_counted, teller_rewired,
    open_account_altered, open_queue_reordered)}
CONTROLS = STEP_FAULTS + tuple(READING_FAULTS)

# control -> (the limits it must trip, those it may trip besides)
CAUGHT_BY = {
    # another order gives another balance and another count of rejections,
    # with the ledger kept and nothing lost
    "neighbours_swapped": ({"accounts_wrong"}, set()),
    # sums in place of the fold: the ledger adds up, no Withdraw is rejected
    # and balances may go below zero
    "folded_commutatively": ({"accounts_wrong"}, {"negative_balances"}),
    # the same commands in another order; what is left over differs where a
    # mailbox overflows at the chunk's last step
    "spill_behind_fresh": ({"accounts_wrong"}, {"queue_wrong"}),
    # the account is one command short, or its queue is where it has not
    # caught up by the chunk's end
    "spill_lost": ({"messages_unaccounted"},
                   {"accounts_wrong", "queue_wrong"}),
    "applied_twice": ({"accounts_wrong", "messages_unaccounted"}, set()),
    # in order and lossless, so only the last step's shows: one more
    # applied, one fewer waiting
    "slot_cap_ignored": ({"accounts_wrong", "queue_wrong"}, set()),
    "withdraw_unchecked": ({"accounts_wrong", "negative_balances"}, set()),
    "deposit_not_booked": ({"accounts_wrong", "ledger_wrong"}, set()),
    "drop_counted": ({"messages_dropped"}, set()),
    "teller_rewired": ({"tellers_wrong"}, set()),
    "open_account_altered": ({"accounts_wrong_at_open"}, set()),
    "open_queue_reordered": ({"queue_wrong_at_open"}, set()),
}


class Case:
    """What a control may read: the run it stands in."""

    def __init__(self, tellers, traffic, n_accounts, slots, spill_capacity,
                 got):
        self.tellers, self.traffic = tellers, traffic
        self.n_accounts, self.slots, self.got = n_accounts, slots, got
        self.spill_capacity = int(spill_capacity)
        self.t0 = int(got["close"]["steps"])
        self.steps = int(got["after"]["steps"]) - self.t0
        if self.steps < 2:
            raise ValueError("the controls need a chunk of two steps or more")

    def rerun(self, fault: str) -> dict:
        """The chunk after the window, from what the window left, by the
        rule with `fault` in it: the `after` reading it leaves."""
        close = self.got["close"]
        accounts = {k: ref.signed(close["accounts"][k])
                    for k in ref.ACCOUNT_COLUMNS}
        queue, _ = ref.queue_of(close, self.n_accounts)
        # what the window left over lies in the spill region, at the front
        queue["carried"] = int(np.asarray(
            close["inbox_valid"], bool)[:self.spill_capacity].sum())
        state = {}
        accounts, queue, _ = ref.replay(
            accounts, queue, self.t0, self.steps, self.tellers, self.traffic,
            self.slots, step_fn=step_with(fault, state))
        if not state.get("done"):
            raise NothingToBreak(f"{fault}: no step of the chunk offered a "
                                 f"place to plant it")
        return _reading(accounts, queue, self.t0 + self.steps, self.tellers)


def reference_outcome(tellers, traffic, n_accounts, slots, got,
                      expected) -> dict:
    """What a faultless system leaves behind where `got`'s left off: the
    opening as the replay from the seed gives it, the close as it is, the
    chunk after it as the reference runs it."""
    accounts, queue, _ = expected["open"]
    after_accounts, after_queue, _ = expected["after"]
    return {"open": _reading(accounts, queue, got["open"]["steps"]),
            "close": got["close"],  # read by every control, altered by none
            "after": _reading(after_accounts, after_queue,
                              got["after"]["steps"], tellers),
            "dropped": 0}


def judge_controls(tellers, traffic, n_accounts, slots, spill_capacity, got,
                   limits) -> dict:
    """Every control, and the unbroken reference in the program's place
    (`reference_itself`, the one that has to come out correct). `got`: a
    run's three readings as `bank.judge` takes them; only `close` and the
    step counts are read."""
    expected = ref.expect(tellers, traffic, n_accounts, slots, got)
    case = Case(tellers, traffic, n_accounts, slots, spill_capacity, got)
    out = {}
    for name in CONTROLS + ("reference_itself",):
        stand_in = reference_outcome(tellers, traffic, n_accounts, slots,
                                     got, expected)
        if name in STEP_FAULTS:
            stand_in["after"] = case.rerun(name)
        elif name in READING_FAULTS:
            READING_FAULTS[name](stand_in, case)
        out[name] = ref.judge(tellers, traffic, n_accounts, slots, stand_in,
                              limits, expected)
    return out


def caught_as_named(name: str, numbers: dict) -> bool:
    """Did the control come out not correct by its limits and no other?"""
    wrong = {k for k, c in numbers.items() if c["value"] > c["limit"]}
    must, may = CAUGHT_BY[name]
    return must <= wrong <= must | may
