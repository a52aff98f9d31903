"""The bank-transfer cell's controls: the reference put in the program's
place with ONE of the configuration's guarantees broken. Each has to come
out as not correct by the limits named for it here (`CAUGHT_BY`) and no
other, or the comparison that decides `correct` proves nothing. Every
number compared is exact, with the limit 0.

A step control runs the chunk after the window from what the window left
(`got["close"]`, on the chip the program's own state after a real window)
by the reference's rule with one part of it broken; a reading control
alters what the faultless reference left the way the fault it names would.
A control raises `NothingToBreak` where the run gives it nothing to break.

`benchmark/tools/control_transfer.py` runs them on the chip at the cell's
own size; tests/benchmark runs them at a toy size."""

from __future__ import annotations

import numpy as np

from benchmark.reference import bank_transfer as ref


class NothingToBreak(ValueError):
    """The run holds no place where this control's fault would show."""


# ------------------------------------------------ one part of the rule broken
def _self_tell_replay(state):
    """Savina's manual stash: out of reply mode, ONE stashed message a step
    is told again to the account itself (the next step's mail, behind what
    is there), and the mail is handled as it comes, the stash or not."""
    def turns(world, rule, a, box, out, counts, handle=ref.handle,
              budget=None):
        acc = world.accounts
        stash = world.stash.get(a)
        used = 0
        if stash and not acc["in_reply"][a]:
            out.append(stash.pop(0))
            if not stash:
                del world.stash[a]
            used = 1
            state["done"] = True
        mail, i = box[:rule.slots], 0
        while i < len(mail):
            msg = mail[i]
            if acc["in_reply"][a] and msg[1] != ref.REPLY:
                world.stash.setdefault(a, []).append(msg)
                counts["stashed"] += 1
                i += 1
                continue
            tells = msg[1] != ref.REPLY or acc["in_reply"][a]
            if used + tells > rule.budget:
                break
            told = handle(acc, a, msg)
            if told is not None:
                out.append(told)
                used += 1
            i += 1
        return mail[i:] + box[rule.slots:]
    return turns


def _budget_ignored(state):
    """Every message of the mailbox's slots handled, whatever it sends."""
    def turns(world, rule, a, box, out, counts, handle=ref.handle,
              budget=None):
        before = len(out)
        rest = ref.account_turns(world, rule, a, box, out, counts, handle,
                                 budget=1 << 30)
        if len(out) - before > rule.budget:
            state["done"] = True
        return rest
    return turns


def _reply_before_debit(state):
    """The destination replies to a Debit and books its amount only at its
    next step: for a step the money is neither in a balance nor in flight."""
    owed = []  # (step, account, amount)

    def turns(world, rule, a, box, out, counts, handle=ref.handle,
              budget=None):
        def late(acc, b, msg):
            if msg[1] == ref.DEBIT:
                owed.append((world.t, b, msg[2]))
                acc["balance"][b] -= msg[2]  # booked at the next step
                state["done"] = True
            return ref.handle(acc, b, msg)

        while owed and owed[0][0] < world.t:
            _, b, amount = owed.pop(0)
            world.accounts["balance"][b] += amount
        return ref.account_turns(world, rule, a, box, out, counts, late)
    return turns


def _stash_lifo(state):
    """The stash replayed newest first, where that changes what is handled:
    a stash of two or more that holds a Credit (Debits alone commute: two
    Replies to two sources in the other order leave the same accounts and,
    a step later, the same mailboxes)."""
    def turns(world, rule, a, box, out, counts, handle=ref.handle,
              budget=None):
        stash = world.stash.get(a)
        if stash and len(stash) > 1 and not world.accounts["in_reply"][a] \
                and any(m[1] == ref.CREDIT for m in stash):
            stash.reverse()
            state["done"] = True
        return ref.account_turns(world, rule, a, box, out, counts)
    return turns


STEP_FAULTS = {"self_tell_replay": _self_tell_replay,
               "budget_ignored": _budget_ignored,
               "reply_before_debit": _reply_before_debit,
               "stash_lifo": _stash_lifo}


# ------------------------------------------------------ a reading altered
def stash_entry_lost(got, case):
    st = got["after"]["stash"]
    full = np.flatnonzero(st["stash_size"] > 0)
    if not full.size:
        raise NothingToBreak("no stash holds a message after the chunk")
    st["stash_size"][full[0]] -= 1


def drop_counted(got, case):
    got["dropped"] = 1


def stash_overflowed(got, case):
    got["counters"]["stash_overflow"] = 1


def reply_mismatched(got, case):
    got["after"]["tellers"]["mismatch"][0] += 1


def reply_out_of_mode(got, case):
    got["after"]["accounts"]["violations"][0] += 1


def open_queue_reordered(got, case):
    snap = got["open"]
    rows = np.flatnonzero(snap["inbox_valid"])
    if rows.size < 2:
        raise NothingToBreak("fewer than two messages wait at the opening")
    a, b = rows[0], rows[-1]
    for k in ("inbox_dst", "inbox_type", "inbox_payload"):
        snap[k][[a, b]] = snap[k][[b, a]]


READING_FAULTS = {f.__name__: f for f in (
    stash_entry_lost, drop_counted, stash_overflowed, reply_mismatched,
    reply_out_of_mode, open_queue_reordered)}
CONTROLS = tuple(STEP_FAULTS) + tuple(READING_FAULTS)

# control -> (the limits it must trip, those it may trip besides); where
# the first is empty, at least one of the second
ORDER = {"queue_wrong", "accounts_wrong", "tellers_wrong", "stashes_wrong"}
CAUGHT_BY = {
    # another order of handling: what the accounts tell, in which order,
    # and which transfers complete when: the replay from what the window
    # left sees it in one or more of the four (which ones depends on where
    # the chunk ends)
    "self_tell_replay": (set(), ORDER),
    "budget_ignored": (set(), ORDER),
    "stash_lifo": (set(), ORDER),
    # the last step's Debits are neither booked nor in flight
    "reply_before_debit": ({"ledger_wrong", "accounts_wrong"}, set()),
    "stash_entry_lost": ({"stashes_wrong", "messages_unaccounted"},
                         {"ledger_wrong"}),
    "drop_counted": ({"messages_dropped"}, set()),
    "stash_overflowed": ({"stash_overflow"}, set()),
    "reply_mismatched": ({"teller_mismatch", "tellers_wrong",
                          "messages_unaccounted"}, set()),
    "reply_out_of_mode": ({"protocol_violations", "accounts_wrong",
                           "messages_unaccounted"}, set()),
    "open_queue_reordered": ({"queue_wrong_at_open"}, set()),
}


# ---------------------------------------------------------- the stand-in
def reading_of(world: ref.World, rule: ref.Rule) -> dict:
    """A World as a system's reading."""
    n = rule.n_accounts
    size = np.zeros(n, np.int64)
    types = np.zeros((n, rule.cap), np.int64)
    payload = np.zeros((n, rule.cap, ref.WIDTH), np.float64)
    for a, msgs in world.stash.items():
        size[a] = len(msgs)
        for j, m in enumerate(msgs):
            types[a, j] = m[1]
            payload[a, j] = m[2:]
    q = np.asarray(world.queue, np.int64).reshape(-1, 2 + ref.WIDTH)
    return {"accounts": {k: np.asarray(v, np.int64)
                         for k, v in world.accounts.items()},
            "stash": {"stash_type": types, "stash_payload": payload,
                      "stash_head": np.zeros(n, np.int64),
                      "stash_size": size},
            "tellers": {k: v.copy() for k, v in world.tellers.items()},
            "inbox_dst": q[:, 0], "inbox_type": q[:, 1],
            "inbox_payload": q[:, 2:].astype(np.float64),
            "inbox_valid": np.ones(q.shape[0], bool), "steps": world.t}


class Case:
    """What a control may read: the run it stands in."""

    def __init__(self, keys, rule, got):
        self.keys, self.rule, self.got = keys, rule, got
        self.t0 = int(got["close"]["steps"])
        self.steps = int(got["after"]["steps"]) - self.t0
        if self.steps < 2:
            raise ValueError("the controls need a chunk of two steps or more")

    def rerun(self, fault: str) -> dict:
        """The chunk after the window, from what the window left, by the
        rule with `fault` in it: the `after` reading it leaves."""
        state = {}
        world = ref.world_of(self.got["close"], self.keys)
        ref.replay(world, self.rule, self.steps, STEP_FAULTS[fault](state))
        if not state.get("done"):
            raise NothingToBreak(f"{fault}: no step of the chunk offered a "
                                 f"place to plant it")
        return reading_of(world, self.rule)


def reference_outcome(rule, got, expected) -> dict:
    """What a faultless system leaves where `got`'s left off."""
    return {"open": reading_of(expected["open"], rule),
            "close": got["close"],  # read by every control, altered by none
            "after": reading_of(expected["after"], rule),
            "dropped": 0, "counters": {"stash_overflow": 0}}


def judge_controls(keys, rule, got, limits) -> dict:
    """Every control, and the unbroken reference in the program's place
    (`reference_itself`, the one that has to come out correct)."""
    expected = ref.expect(keys, rule, got)
    case = Case(keys, rule, got)
    out = {}
    for name in CONTROLS + ("reference_itself",):
        stand_in = reference_outcome(rule, got, expected)
        if name in STEP_FAULTS:
            stand_in["after"] = case.rerun(name)
        elif name in READING_FAULTS:
            READING_FAULTS[name](stand_in, case)
        out[name] = ref.judge(keys, rule, stand_in, limits, expected)
    return out


def caught_as_named(name: str, numbers: dict) -> bool:
    """Did the control come out not correct by its limits and no other?"""
    wrong = {k for k, c in numbers.items() if c["value"] > c["limit"]}
    must, may = CAUGHT_BY[name]
    return bool(wrong) and must <= wrong <= must | may
