"""The sharded bank-transfer cell's controls: the reference put in the
program's place with ONE of the configuration's guarantees broken, as
benchmark/reference/bank_transfer_controls.py does on one chip (whose
docstring holds here too). Each has to come out as not correct by the limits
named for it (`CAUGHT_BY`) and no other. Besides the account's own rule (the
stash, the budget, the order of a transfer's chain) they break what the
cluster adds: the order of the source chips' chunks, the leftovers ahead of
the fresh mail on every chip, a pair's provisioned capacity with its drops
counted or not, where an id lives, and the counters of what was lost.

`benchmark/tools/control_xtransfer.py` runs them on the chips at the cell's
own size; tests/benchmark runs them at a toy size."""

from __future__ import annotations

import numpy as np

from benchmark.reference import bank_transfer as one_chip
from benchmark.reference import bank_transfer_sharded as ref
from benchmark.reference.bank_transfer_controls import (
    NothingToBreak, _budget_ignored, _self_tell_replay)

VICTIM = 1  # the chip, and the pair (VICTIM - 1 -> VICTIM), a fault picks


# ------------------------------------------------ one part of the rule broken
def _reply_before_debit(state, dep):
    """The destination replies to a Debit and books its amount only at its
    next step: for a step the money is neither in a balance nor in flight."""
    owed = []  # (step, account, amount)

    def turns(world, rule, a, box, out, counts, handle, budget=None):
        def late(acc, b, msg):
            if msg[1] == ref.DEBIT:
                owed.append((world.t, b, msg[2]))
                acc["balance"][b] -= msg[2]  # booked at the next step
                state["done"] = True
            return handle(acc, b, msg)

        while owed and owed[0][0] < world.t:
            _, b, amount = owed.pop(0)
            world.accounts["balance"][b] += amount
        return one_chip.account_turns(world, rule, a, box, out, counts, late)
    return {"turns": turns}


def _stash_lifo(state, dep):
    """The stash replayed newest first as it opens, where that changes what
    is handled (a stash of two or more that holds a Credit)."""
    def turns(world, rule, a, box, out, counts, handle, budget=None):
        def lifo(acc, b, msg):
            told = handle(acc, b, msg)
            stash = world.stash.get(b)
            if not acc["in_reply"][b] and stash and len(stash) > 1 \
                    and any(m[1] == ref.CREDIT for m in stash):
                stash.reverse()
                state["done"] = True
            return told
        return one_chip.account_turns(world, rule, a, box, out, counts, lifo)
    return {"turns": turns}


def _one_chip_fault(make):
    """A fault of the one-chip controls, whose turns take the handle."""
    return lambda state, dep: {"turns": make(state)}


def _chunks_reversed(state, dep):
    """Every chip's fresh mail by source chip in reverse order."""
    state["done"] = dep.chips > 1
    return {"chunk_order": range(dep.chips - 1, -1, -1)}


def _fresh_ahead_of_leftovers(state, dep):
    """The fresh mail ahead of what the chip's accounts left over."""
    state["needs_leftovers"] = True
    return {"fresh_first": True}


def _pair_cut(state, dep):
    """Pair (VICTIM - 1 -> VICTIM) carries one message a step."""
    victim = VICTIM % dep.chips
    pair = ((victim - 1) % dep.chips, victim)
    state["needs_cut"] = True
    return {"capacity_of": lambda s, d: 1 if (s, d) == pair
            else dep.pair_capacity}


def _placement_off_by_one(state, dep):
    """An account tells its Debit, and its Reply to a source, to the row of
    the NEXT id: the message reaches whoever lives there."""
    def handle(acc, a, msg):
        told = one_chip.handle(acc, a, msg)
        if told is None or msg[1] == ref.REPLY:
            return told
        state["done"] = True
        return (dep.row_of[(told[0] + 1) % dep.n_accounts],) + told[1:]
    return {"handle": handle}


STEP_FAULTS = {"self_tell_replay": _one_chip_fault(_self_tell_replay),
               "budget_ignored": _one_chip_fault(_budget_ignored),
               "reply_before_debit": _reply_before_debit,
               "stash_lifo": _stash_lifo,
               "chunks_reversed": _chunks_reversed,
               "fresh_ahead_of_leftovers": _fresh_ahead_of_leftovers,
               "pair_cut_uncounted": _pair_cut,
               "pair_cut_counted": _pair_cut,
               "placement_off_by_one": _placement_off_by_one}


# ------------------------------------------------------ a reading altered
def _valid_rows(snap, dep, chip):
    rows = np.flatnonzero(snap["inbox_valid"][
        chip * dep.block_rows:(chip + 1) * dep.block_rows])
    return rows + chip * dep.block_rows


def stash_entry_lost(got, case):
    st = got["after"]["stash"]
    full = np.flatnonzero(st["stash_size"] > 0)
    if not full.size:
        raise NothingToBreak("no stash holds a message after the chunk")
    st["stash_size"][full[0]] -= 1


def stash_overflowed(got, case):
    """A stash's newest message lost, and counted."""
    st = got["after"]["stash"]
    full = np.flatnonzero(st["stash_size"] > 0)
    if not full.size:
        raise NothingToBreak("no stash holds a message after the chunk")
    st["stash_size"][full[0]] -= 1
    got["after"]["counters"]["stash_overflow"] += 1


def _row_lost_and_counted(got, case, counter):
    snap = got["after"]
    rows = _valid_rows(snap, case.dep, VICTIM % case.dep.chips)
    if not rows.size:
        raise NothingToBreak("the victim chip's inbox is empty")
    snap["inbox_valid"][rows[-1]] = False
    snap["counters"][counter] += 1


def spill_lost_counted(got, case):
    """A message lost to a full spill region, and counted."""
    _row_lost_and_counted(got, case, "mail_dropped")


def exchange_lost_counted(got, case):
    """A message lost to a full pair chunk, and counted."""
    _row_lost_and_counted(got, case, "exchange_dropped")


def reply_mismatched(got, case):
    got["after"]["tellers"]["mismatch"][0] += 1


def reply_out_of_mode(got, case):
    got["after"]["accounts"]["violations"][0] += 1


def open_queue_reordered(got, case):
    snap = got["open"]
    rows = _valid_rows(snap, case.dep, VICTIM % case.dep.chips)
    if rows.size < 2:
        raise NothingToBreak("fewer than two messages wait at the opening")
    a, b = rows[0], rows[-1]
    for k in ("inbox_dst", "inbox_type", "inbox_payload"):
        snap[k][[a, b]] = snap[k][[b, a]]


READING_FAULTS = {f.__name__: f for f in (
    stash_entry_lost, stash_overflowed, spill_lost_counted,
    exchange_lost_counted, reply_mismatched, reply_out_of_mode,
    open_queue_reordered)}
CONTROLS = tuple(STEP_FAULTS) + tuple(READING_FAULTS)

# control -> (the limits it must trip, those it may trip besides); where
# the first is empty, at least one of the second
ORDER = {"queue_wrong", "accounts_wrong", "tellers_wrong", "stashes_wrong"}
CAUGHT_BY = {
    # another order of handling: what the accounts tell, in which order,
    # and which transfers complete when (which of the four sees it depends
    # on where the chunk ends)
    "self_tell_replay": (set(), ORDER),
    "budget_ignored": (set(), ORDER),
    "stash_lifo": (set(), ORDER),
    "chunks_reversed": (set(), ORDER),
    "fresh_ahead_of_leftovers": (set(), ORDER),
    # the last step's Debits are neither booked nor in flight
    "reply_before_debit": ({"ledger_wrong", "accounts_wrong"}, set()),
    # messages gone that no counter owns up to; the same loss counted is
    # accounted for, and not allowed (and its counter differs from the
    # replay's, `queue_wrong`)
    "pair_cut_uncounted": ({"messages_unaccounted"},
                           ORDER | {"ledger_wrong"}),
    "pair_cut_counted": ({"messages_dropped", "exchange_dropped"},
                         ORDER | {"ledger_wrong"}),
    # every Debit and Reply to a source handled by the wrong account
    "placement_off_by_one": ({"accounts_wrong"},
                             ORDER | {"protocol_violations"}),
    "stash_entry_lost": ({"stashes_wrong", "messages_unaccounted"},
                         {"ledger_wrong"}),
    "stash_overflowed": ({"stash_overflow", "stashes_wrong",
                          "queue_wrong"}, {"ledger_wrong"}),
    "spill_lost_counted": ({"messages_dropped", "queue_wrong"},
                           {"ledger_wrong"}),
    "exchange_lost_counted": ({"messages_dropped", "exchange_dropped",
                               "queue_wrong"}, {"ledger_wrong"}),
    "reply_mismatched": ({"teller_mismatch", "tellers_wrong",
                          "messages_unaccounted"}, set()),
    "reply_out_of_mode": ({"protocol_violations", "accounts_wrong",
                           "messages_unaccounted"}, set()),
    "open_queue_reordered": ({"queue_wrong_at_open"}, set()),
}


# ---------------------------------------------------------- the stand-in
def reading_of(world: ref.World, dep: ref.Deployment, counters: dict) -> dict:
    """A World as a system's reading: every chip's queue laid out in its
    block as `world.rows` says (leftovers in the spill region, a chunk a
    source chip), the stashes as rings with their oldest first."""
    n = dep.n_accounts
    width = max([1] + [len(m) for m in world.stash.values()])
    size = np.zeros(n, np.int64)
    types = np.zeros((n, width), np.int64)
    payload = np.zeros((n, width, ref.WIDTH), np.float64)
    for a, msgs in world.stash.items():
        size[a] = len(msgs)
        for j, m in enumerate(msgs):
            types[a, j] = m[1]
            payload[a, j] = m[2:]
    rows = dep.block_rows * dep.chips
    dst = np.full(rows, -1, np.int64)
    kind = np.zeros(rows, np.int64)
    body = np.zeros((rows, ref.WIDTH), np.float64)
    valid = np.zeros(rows, bool)
    starts = [0] + [dep.rule.spill + s * dep.pair_capacity
                    for s in range(dep.chips)]
    for c, (queue, layout) in enumerate(zip(world.queues, world.rows)):
        msgs = iter(queue)
        for start, count in zip(starts, layout):
            for j in range(count):
                m = next(msgs)
                at = c * dep.block_rows + start + j
                dst[at], kind[at], body[at] = m[0], m[1], m[2:]
                valid[at] = True
    return {"accounts": {k: np.asarray(v, np.int64)
                         for k, v in world.accounts.items()},
            "stash": {"stash_type": types, "stash_payload": payload,
                      "stash_head": np.zeros(n, np.int64),
                      "stash_size": size},
            "tellers": {k: v.copy() for k, v in world.tellers.items()},
            "inbox_dst": dst, "inbox_type": kind, "inbox_payload": body,
            "inbox_valid": valid, "steps": world.t,
            "counters": {k: counters[k] for k in ref.COUNTERS}}


class Case:
    """What a control may read: the run it stands in."""

    def __init__(self, keys, dep, got):
        self.keys, self.dep, self.got = keys, dep, got
        self.t0 = int(got["close"]["steps"])
        self.steps = int(got["after"]["steps"]) - self.t0
        if self.steps < 2:
            raise ValueError("the controls need a chunk of two steps or more")

    def rerun(self, fault: str) -> dict:
        """The chunk after the window, from what the window left, by the
        rule with `fault` in it: the `after` reading it leaves."""
        state = {}
        broken = STEP_FAULTS[fault](state, self.dep)
        world = ref.world_of(self.got["close"], self.dep, self.keys)
        chunk = ref.zero_counters()
        for _ in range(self.steps):
            before = chunk["exchange_dropped"]
            counts = ref.step(world, self.dep, chunk, **broken)
            if state.get("needs_leftovers") and counts["left"]:
                state["done"] = True
            if state.get("needs_cut") and chunk["exchange_dropped"] > before:
                state["done"] = True
                if fault == "pair_cut_uncounted":
                    chunk["exchange_dropped"] = before
        if not state.get("done"):
            raise NothingToBreak(f"{fault}: no step of the chunk offered a "
                                 f"place to plant it")
        return reading_of(world, self.dep,
                          ref.advance(self.got["close"]["counters"], chunk))


def reference_outcome(dep, got, expected) -> dict:
    """What a faultless system leaves where `got`'s left off."""
    return {"open": reading_of(expected["open"], dep,
                               expected["open_counters"]),
            "close": got["close"],  # read by every control, altered by none
            "after": reading_of(expected["after"], dep,
                                expected["after_counters"])}


def judge_controls(keys, dep, got, limits) -> dict:
    """Every control, and the unbroken reference in the program's place
    (`reference_itself`, the one that has to come out correct)."""
    expected = ref.expect(keys, dep, got)
    case = Case(keys, dep, got)
    out = {}
    for name in CONTROLS + ("reference_itself",):
        stand_in = reference_outcome(dep, got, expected)
        if name in STEP_FAULTS:
            stand_in["after"] = case.rerun(name)
        elif name in READING_FAULTS:
            READING_FAULTS[name](stand_in, case)
        out[name] = ref.judge(keys, dep, stand_in, limits, expected)
    return out


def caught_as_named(name: str, numbers: dict) -> bool:
    """Did the control come out not correct by its limits and no other?"""
    wrong = {k for k, c in numbers.items() if c["value"] > c["limit"]}
    must, may = CAUGHT_BY[name]
    return bool(wrong) and must <= wrong <= must | may
