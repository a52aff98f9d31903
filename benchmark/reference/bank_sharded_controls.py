"""The sharded bank's controls: the reference put in the program's place with
ONE of the configuration's guarantees broken, as
benchmark/reference/bank_controls.py does for the one-chip bank (whose
docstring holds here too). Each has to come out as not correct, by the limits
named for it (`CAUGHT_BY`) and no other. What is new on the mesh is what the
mesh added to the guarantees: the order of the source chips' chunks, the
leftovers ahead of the fresh mail on every chip, a pair's provisioned
capacity with its drops counted, where an id lives, and a spill region a
chip.

`benchmark/tools/control_xbank.py` runs them on the chips at the cell's own
size; tests/benchmark runs them at toy sizes."""

from __future__ import annotations

import numpy as np

from benchmark.reference import bank as one_chip
from benchmark.reference import bank_sharded as ref
from benchmark.reference.bank_controls import (NothingToBreak,
                                               _consume_commutatively)

VICTIM = 1  # the chip, and the pair (VICTIM - 1 -> VICTIM), a fault picks


def step_with(fault: str, state: dict):
    """A `step` of the reference with one stage broken. `state["kept"]`: the
    leftovers at the front of every chip's queue; `state["done"]`: whether
    the fault found a place yet."""

    def step(accounts, queues, t, tellers, traffic, dep, counters):
        told = ref.emissions(t, tellers, dep, traffic)
        if fault == "placement_off_by_one_shard":
            # the sender computes the row of the NEXT shard's account: the
            # command reaches whoever lives there
            wrong = ref.Deployment(
                dep.n_accounts, dep.n_tellers, dep.chips, dep.shards,
                dep.slots, dep.spill_capacity, dep.pair_capacity,
                dep.host_rows, shift=1)
            told["account"] = dep.account_at[
                wrong.account_row[told["account"]]]
            state["done"] = True
        out, counters["rows"] = [], [[] for _ in queues]
        victim = VICTIM % dep.chips
        for chip, queue in enumerate(queues):
            kept = state["kept"][chip]
            if fault == "fresh_ahead_of_leftovers" and kept:
                m = queue["account"].shape[0]
                queue = one_chip.take(queue, np.concatenate(
                    [np.arange(kept, m), np.arange(kept)]))
                state["done"] = True
            ordered, place = ref.mailboxes(queue, dep)
            if fault == "folded_commutatively":
                accounts = _consume_commutatively(accounts, ordered, place,
                                                  dep.slots)
                state["done"] = True
            else:
                accounts = one_chip.consume(accounts, ordered, place,
                                            dep.slots)
            left = one_chip.take(ordered,
                                 np.flatnonzero(place >= dep.slots))
            if fault == "chip_spill_lost" and chip == victim \
                    and not state.get("done") and left["account"].shape[0]:
                left = one_chip.empty_queue()  # and nobody counts it
                state["done"] = True
            n = min(left["account"].shape[0], dep.spill_capacity)
            counters["spill_lost"] += left["account"].shape[0] - n
            counters["spilled"] += n
            counters["spill_high_water"] = max(counters["spill_high_water"],
                                               n)
            counters["rows"][chip].append(n)
            order = capacity_of = None
            count_in = counters
            if fault == "chunks_reversed":
                order = range(dep.chips - 1, -1, -1)
                state["done"] = dep.chips > 1
            if fault in ("pair_cut_uncounted", "pair_cut_counted") \
                    and chip == victim:
                asked = int((dep.account_chip[told["account"]][
                    (victim - 1) % dep.chips * dep.tellers_a_chip:][
                    :dep.tellers_a_chip] == chip).sum())
                cut = asked // 2  # a capacity half of what the pair sends

                def capacity_of(s, cut=cut):
                    return cut if s == (victim - 1) % dep.chips \
                        else dep.pair_capacity
                state["done"] = state.get("done") or asked > cut
                if fault == "pair_cut_uncounted":
                    count_in = dict(ref.zero_counters(),
                                    rows=counters["rows"])
            fresh = ref.arrivals(told, chip, dep, count_in, order,
                                 capacity_of)
            if count_in is not counters:  # the high water is still kept
                counters["exchange_high_water"] = max(
                    counters["exchange_high_water"],
                    count_in["exchange_high_water"])
            out.append(one_chip.join(one_chip.take(left, slice(0, n)),
                                     fresh))
            state["kept"][chip] = n
        return accounts, out

    return step


STEP_FAULTS = ("chunks_reversed", "fresh_ahead_of_leftovers",
               "pair_cut_uncounted", "pair_cut_counted",
               "folded_commutatively", "placement_off_by_one_shard",
               "chip_spill_lost")


# ------------------------------------------------------ a reading laid out
def _reading(accounts, queues, rows, steps, counters, dep, tellers=None):
    """Accounts by id and every chip's queue, as a reading of the system:
    the columns by row, the inbox a block a chip (`rows[chip]`: leftovers,
    then a count a source chip's chunk)."""
    total = dep.rows_a_chip * dep.chips
    state = {k: np.zeros(total, np.int64) for k in ref.ACCOUNT_COLUMNS}
    for k in ref.ACCOUNT_COLUMNS:
        state[k][dep.account_row] = accounts[k]
    if tellers is not None:
        for k in ref.TELLER_COLUMNS:
            state[k] = np.zeros(total, np.int64)
            state[k][dep.teller_row] = tellers[k]
    m = dep.block_rows * dep.chips
    snap = {"state": state, "steps": int(steps), "counters": dict(counters),
            "inbox_dst": np.full(m, -1, np.int64),
            "inbox_type": np.zeros(m, np.int64),
            "inbox_payload": np.zeros((m, one_chip.WIDTH), np.float32),
            "inbox_valid": np.zeros(m, bool)}
    for chip, queue in enumerate(queues):
        starts = [0] + [dep.spill_capacity + s * dep.pair_capacity
                        for s in range(dep.chips)]
        at = np.concatenate([np.arange(n) + start
                             for n, start in zip(rows[chip], starts)]
                            or [np.zeros(0, np.int64)]).astype(np.int64)
        at += chip * dep.block_rows
        snap["inbox_dst"][at] = dep.account_row[queue["account"]]
        snap["inbox_type"][at] = queue["kind"]
        snap["inbox_payload"][at, 0] = queue["amount"]
        snap["inbox_payload"][at, 1] = queue["teller"]
        snap["inbox_valid"][at] = True
    return snap


def _counters_after(before: dict, chunk: dict) -> dict:
    """The device's counters a chunk later: the replay's own added."""
    return {"mail_dropped": before["mail_dropped"] + chunk["spill_lost"],
            "exchange_dropped": before["exchange_dropped"]
            + chunk["exchange_lost"],
            "spilled": (before["spilled"] + chunk["spilled"]) % one_chip.WRAP,
            "spill_high_water": max(before["spill_high_water"],
                                    chunk["spill_high_water"]),
            "exchange_high_water": max(before["exchange_high_water"],
                                       chunk["exchange_high_water"])}


# ------------------------------------------------------ a reading altered
def _richest(snap, dep) -> int:
    return int(dep.account_row[np.argmax(
        ref.accounts_of(snap, dep)["balance"])])


def withdraw_unchecked(got, case):
    state = got["after"]["state"]
    row = _richest(got["after"], case.dep)
    more = int(one_chip.signed(state["balance"])[row]) + 1
    state["balance"][row] -= more
    state["withdrawn"][row] += more


def deposit_not_booked(got, case):
    got["after"]["state"]["balance"][_richest(got["after"], case.dep)] += 1


def teller_rewired(got, case):
    dep = case.dep
    row = dep.teller_row[dep.n_tellers // 3]
    home = got["after"]["state"]["home"]
    home[row] = (home[row] + 1) % dep.n_accounts


def open_account_altered(got, case):
    state, row = got["open"]["state"], case.dep.account_row[0]
    state["balance"][row] += 1
    state["deposited"][row] += 1


def open_queue_reordered(got, case):
    """Two waiting commands of one chip changed places before the window
    opened."""
    snap = got["open"]
    rows = np.flatnonzero(snap["inbox_valid"][:case.dep.block_rows])
    if rows.size < 2:
        raise NothingToBreak("fewer than two commands wait on chip 0")
    a, b = rows[0], rows[-1]
    for k in ("inbox_dst", "inbox_type", "inbox_payload"):
        snap[k][[a, b]] = snap[k][[b, a]]


READING_FAULTS = {f.__name__: f for f in (
    withdraw_unchecked, deposit_not_booked, teller_rewired,
    open_account_altered, open_queue_reordered)}
CONTROLS = STEP_FAULTS + tuple(READING_FAULTS)

# control -> (the limits it must trip, those it may trip besides)
CAUGHT_BY = {
    # the same commands to every account, those of different source chips
    # in another order; what is left over differs where a mailbox overflows
    # at the chunk's last step, and the fresh mail lies in another order
    "chunks_reversed": ({"accounts_wrong", "queue_wrong"}, set()),
    "fresh_ahead_of_leftovers": ({"accounts_wrong"}, {"queue_wrong"}),
    # commands gone that no counter owns up to
    "pair_cut_uncounted": ({"messages_unaccounted"},
                           {"accounts_wrong", "queue_wrong"}),
    # the same loss, counted: accounted for, and not allowed
    "pair_cut_counted": ({"messages_dropped", "exchange_dropped"},
                         {"accounts_wrong", "queue_wrong"}),
    "folded_commutatively": ({"accounts_wrong"}, {"negative_balances"}),
    # every command applied once, to the wrong account
    "placement_off_by_one_shard": ({"accounts_wrong", "queue_wrong"}, set()),
    "chip_spill_lost": ({"messages_unaccounted"},
                        {"accounts_wrong", "queue_wrong"}),
    "withdraw_unchecked": ({"accounts_wrong", "negative_balances"}, set()),
    "deposit_not_booked": ({"accounts_wrong", "ledger_wrong"}, set()),
    "teller_rewired": ({"tellers_wrong"}, set()),
    "open_account_altered": ({"accounts_wrong_at_open"}, set()),
    "open_queue_reordered": ({"queue_wrong_at_open"}, set()),
}


class Case:
    """What a control may read: the run it stands in."""

    def __init__(self, tellers, traffic, dep, got):
        self.tellers, self.traffic, self.dep, self.got = \
            tellers, traffic, dep, got
        self.t0 = int(got["close"]["steps"])
        self.steps = int(got["after"]["steps"]) - self.t0
        if self.steps < 2:
            raise ValueError("the controls need a chunk of two steps or more")

    def rerun(self, fault: str) -> dict:
        """The chunk after the window, from what the window left, by the
        rule with `fault` in it: the `after` reading it leaves."""
        close, dep = self.got["close"], self.dep
        queues, _ = ref.queues_of(close, dep)
        valid = np.asarray(close["inbox_valid"], bool).reshape(
            dep.chips, dep.block_rows)
        state = {"kept": [int(n) for n in
                          valid[:, :dep.spill_capacity].sum(1)]}
        accounts, queues, counters = ref.replay(
            ref.accounts_of(close, dep), queues, self.t0, self.steps,
            self.tellers, self.traffic, dep, step_fn=step_with(fault, state))
        if not state.get("done"):
            raise NothingToBreak(f"{fault}: no step of the chunk offered a "
                                 f"place to plant it")
        return _reading(accounts, queues, counters["rows"],
                        self.t0 + self.steps,
                        _counters_after(close["counters"], counters), dep,
                        self.tellers)


def reference_outcome(tellers, dep, got, expected) -> dict:
    """What a faultless system leaves behind where `got`'s left off."""
    accounts, queues, at_open = expected["open"]
    after_accounts, after_queues, chunk = expected["after"]
    zero = dict.fromkeys(("mail_dropped", "exchange_dropped", "spilled",
                          "spill_high_water", "exchange_high_water"), 0)
    return {"open": _reading(accounts, queues, at_open["rows"],
                             got["open"]["steps"],
                             _counters_after(zero, at_open), dep),
            "close": got["close"],  # read by every control, altered by none
            "after": _reading(after_accounts, after_queues, chunk["rows"],
                              got["after"]["steps"],
                              _counters_after(got["close"]["counters"],
                                              chunk), dep, tellers)}


def judge_controls(tellers, traffic, dep, got, limits) -> dict:
    """Every control, and the unbroken reference in the program's place
    (`reference_itself`, the one that has to come out correct). `got`: a
    run's three readings as `bank_sharded.judge` takes them; only `close`
    and the step counts are read."""
    expected = ref.expect(tellers, traffic, dep, got)
    case = Case(tellers, traffic, dep, got)
    out = {}
    for name in CONTROLS + ("reference_itself",):
        stand_in = reference_outcome(tellers, dep, got, expected)
        if name in STEP_FAULTS:
            stand_in["after"] = case.rerun(name)
        elif name in READING_FAULTS:
            READING_FAULTS[name](stand_in, case)
        out[name] = ref.judge(tellers, traffic, dep, stand_in, limits,
                              expected)
    return out


def caught_as_named(name: str, numbers: dict) -> bool:
    """Did the control come out not correct by its limits and no other?"""
    wrong = {k for k, c in numbers.items() if c["value"] > c["limit"]}
    must, may = CAUGHT_BY[name]
    return must <= wrong <= must | may
