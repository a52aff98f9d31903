"""Plain reference for the bank over C chips: numpy only, nothing of the
program. The account, the teller's rule and the one-command-at-a-time fold
are benchmark/reference/bank.py's (`emissions`, `apply`, `consume`); what is
here is the cluster: where an entity lives, which queue a command waits in,
and in which order a chip's queue holds them.

Placement (the Akka documentation's Cluster Sharding: an entity's id decides
its shard, `HashCodeMessageExtractor`: id mod S; the S shards are spread
evenly over the C regions, one a chip). With A accounts, T tellers, and
N = (A + T) / C rows a chip, chip c holds the rows [c N, (c + 1) N):

    account a:  shard s = a mod S,  chip c = s div (S / C),
                row  c N + (s mod (S / C)) (A / S) + a div S
    teller i:   chip c = i div (T / C),  row  c N + A / C + i mod (T / C)

so a chip's rows are its accounts, shard by shard, then its tellers, and a
teller's row grows with its index.

The queue of chip c (what its inbox block holds, `judge` compares row for
row): first what its accounts left over at earlier steps, by account ROW and
within an account in the order it had; then the last step's fresh mail for
its accounts BY THE SENDER'S GLOBAL ROW, which is the chunks by source chip,
each in the source's row order. At a step an account takes the commands
waiting for it in queue order, applies the first `mailbox_slots` and leaves
the rest over. A chip keeps at most `spill_capacity` leftovers: the first in
queue order; the rest are LOST and counted (`spill_lost`). A pair (source
chip, destination chip) carries at most `pair_capacity` fresh commands a
step: the first in the source's row order; the rest are LOST and counted
(`exchange_lost`). Neither happens at the cell's capacities; the reference
still says what would. It also counts what the program counts: `spilled`
(leftovers kept, summed over steps and chips), `spill_high_water` (the most
one chip kept after one step), `exchange_high_water` (the most one pair was
asked to carry in one step, before the cut).

Because the tellers keep their relative order, every account meets the same
commands in the same order as benchmark/reference/bank.py's from the same
tellers, whatever C is, as long as nothing is lost: location transparency."""

from __future__ import annotations

import numpy as np

from benchmark.reference import bank as ref

ACCOUNT_COLUMNS, TELLER_COLUMNS = ref.ACCOUNT_COLUMNS, ref.TELLER_COLUMNS
QUEUE_COLUMNS = ref.QUEUE_COLUMNS  # account (its ID), kind, amount, teller
COUNTERS = ("spilled", "spill_high_water", "exchange_high_water")
seed_tellers = ref.seed_tellers


class Deployment:
    """The configuration's numbers, and the placement they give."""

    def __init__(self, n_accounts: int, n_tellers: int, chips: int,
                 shards: int, slots: int, spill_capacity: int,
                 pair_capacity: int, host_rows: int, shift: int = 0):
        self.n_accounts, self.n_tellers = int(n_accounts), int(n_tellers)
        self.chips, self.shards = int(chips), int(shards)
        self.slots = int(slots)
        self.spill_capacity = int(spill_capacity)
        self.pair_capacity = int(pair_capacity)
        self.host_rows = int(host_rows)
        a_chip, t_chip = self.n_accounts // chips, self.n_tellers // chips
        self.rows_a_chip = a_chip + t_chip
        self.tellers_a_chip = t_chip
        # a chip's inbox block: spill region, a chunk a source chip, host rows
        self.block_rows = self.spill_capacity + chips * self.pair_capacity \
            + self.host_rows
        a = np.arange(self.n_accounts)
        shard = (a + shift) % shards  # `shift`: a control's, 0 for the rule
        self.account_chip = shard // (shards // chips)
        self.account_row = self.account_chip * self.rows_a_chip \
            + (shard % (shards // chips)) * (self.n_accounts // shards) \
            + a // shards
        i = np.arange(self.n_tellers)
        self.teller_row = (i // t_chip) * self.rows_a_chip + a_chip \
            + i % t_chip
        self.account_at = np.full(self.rows_a_chip * chips, -1, np.int64)
        self.account_at[self.account_row] = a


def from_config(conf: dict, chips: int) -> Deployment:
    return Deployment(conf["accounts"], conf["tellers"], chips,
                      conf["logical_shards"], conf["mailbox_slots"],
                      conf["spill_capacity"],
                      conf["remote_capacity_per_pair"], conf["host_inbox"])


def emissions(t: int, tellers: dict, dep: Deployment, traffic: dict) -> dict:
    """What the tellers tell at step t, by teller index: bank.py's rule, the
    sender named by its row of THIS layout."""
    told = ref.emissions(t, tellers, dep.n_accounts, traffic)
    told["teller"] = dep.teller_row
    return told


def empty_queues(dep: Deployment) -> list:
    return [ref.empty_queue() for _ in range(dep.chips)]


def zero_counters() -> dict:
    """The counters of a replay; `rows` is how the newest step left every
    chip's queue laid out: [leftovers kept, a count a source chip]."""
    return dict(dict.fromkeys(COUNTERS + ("spill_lost", "exchange_lost"), 0),
                rows=[])


def mailboxes(queue: dict, dep: Deployment):
    """A chip's queue by account ROW, each account's commands in queue
    order, and every command's place in its mailbox (0 for the oldest)."""
    row = dep.account_row[queue["account"]]
    order = np.argsort(row, kind="stable")
    row = row[order]
    place = np.arange(row.shape[0]) - np.searchsorted(row, row, side="left")
    return ref.take(queue, order), place


def arrivals(told: dict, chip: int, dep: Deployment, counters: dict,
             chunk_order=None, capacity_of=None) -> dict:
    """The fresh mail of one step for `chip`, as its inbox receives it: the
    chunk of every source chip in turn (`chunk_order`: a control's; the rule
    is 0, 1, ...), each the source's commands in row order, cut at the
    pair's capacity (`capacity_of(source)`: a control's)."""
    mine = np.flatnonzero(dep.account_chip[told["account"]] == chip)
    source = mine // dep.tellers_a_chip  # teller index order is row order
    starts = np.searchsorted(source, np.arange(dep.chips + 1))
    chunks = []
    for s in (range(dep.chips) if chunk_order is None else chunk_order):
        asked = int(starts[s + 1] - starts[s])
        cap = dep.pair_capacity if capacity_of is None else capacity_of(s)
        counters["exchange_high_water"] = max(
            counters["exchange_high_water"], asked)
        counters["exchange_lost"] += max(asked - cap, 0)
        chunks.append(mine[starts[s]:starts[s] + min(asked, cap)])
    counters["rows"][chip] += [c.shape[0] for c in chunks]
    return ref.take(told, np.concatenate(chunks))


def step(accounts: dict, queues: list, t: int, tellers: dict, traffic: dict,
         dep: Deployment, counters: dict):
    """One literal step of every chip. Returns (accounts, queues); the
    counters are updated in place."""
    told = emissions(t, tellers, dep, traffic)
    out, counters["rows"] = [], [[] for _ in queues]
    for chip, queue in enumerate(queues):
        ordered, place = mailboxes(queue, dep)
        accounts = ref.consume(accounts, ordered, place, dep.slots)
        left = ref.take(ordered, np.flatnonzero(place >= dep.slots))
        kept = min(left["account"].shape[0], dep.spill_capacity)
        counters["spill_lost"] += left["account"].shape[0] - kept
        counters["spilled"] += kept
        counters["spill_high_water"] = max(counters["spill_high_water"], kept)
        counters["rows"][chip].append(kept)
        out.append(ref.join(ref.take(left, slice(0, kept)),
                            arrivals(told, chip, dep, counters)))
    return accounts, out


def replay(accounts: dict, queues: list, t0: int, steps: int, tellers: dict,
           traffic: dict, dep: Deployment, step_fn=step):
    """`steps` steps from step t0 on. Returns (accounts, queues, counters):
    the counters of these steps alone."""
    counters = zero_counters()
    for t in range(t0, t0 + steps):
        accounts, queues = step_fn(accounts, queues, t, tellers, traffic, dep,
                                   counters)
    return accounts, queues, counters


# ------------------------------------------------------------ the verdict
def accounts_of(snap: dict, dep: Deployment) -> dict:
    """A reading's account columns by account ID, read at the rows THIS
    placement gives (int32 columns that may have wrapped, as signed)."""
    return {k: ref.signed(np.asarray(snap["state"][k])[dep.account_row])
            for k in ACCOUNT_COLUMNS}


def queues_of(snap: dict, dep: Deployment):
    """The commands each chip's inbox block holds, in row order, and how
    many valid rows are no command of this deployment: bank.py's tests, and
    an address that is no account of THAT chip. Those are counted, a chip."""
    queues, malformed = [], []
    for chip in range(dep.chips):
        rows = slice(chip * dep.block_rows, (chip + 1) * dep.block_rows)
        block = {k: np.asarray(snap[k])[rows] for k in (
            "inbox_dst", "inbox_type", "inbox_payload", "inbox_valid")}
        dst = np.asarray(block["inbox_dst"], np.int64)
        here = (dst >= chip * dep.rows_a_chip) \
            & (dst < (chip + 1) * dep.rows_a_chip)
        ids = np.where(here, dep.account_at[np.where(here, dst, 0)], -1)
        block["inbox_dst"] = ids  # -1: no account, malformed to bank.py too
        queue, bad = ref.queue_of(block, dep.n_accounts)
        queues.append(queue)
        malformed.append(bad)
    return queues, malformed


def _lost(snap: dict) -> int:
    c = snap["counters"]
    return int(c["mail_dropped"]) + int(c["exchange_dropped"])


def _held(snap: dict, dep: Deployment) -> int:
    """The commands a reading accounts for: applied, waiting, or lost and
    counted."""
    return int(accounts_of(snap, dep)["applied"].sum()) \
        + int(np.asarray(snap["inbox_valid"], bool).sum()) + _lost(snap)


def _spilled_between(a: dict, b: dict) -> int:
    return (int(b["counters"]["spilled"])
            - int(a["counters"]["spilled"])) % ref.WRAP


def _regions(snap: dict, dep: Deployment):
    """How many rows every chip's spill region holds, and every pair
    chunk."""
    valid = np.asarray(snap["inbox_valid"], bool).reshape(
        dep.chips, dep.block_rows)
    chunks = valid[:, dep.spill_capacity:dep.block_rows - dep.host_rows]
    return valid[:, :dep.spill_capacity].sum(1), chunks.reshape(
        dep.chips, dep.chips, dep.pair_capacity).sum(2)


def expect(tellers: dict, traffic: dict, dep: Deployment, got: dict) -> dict:
    """What `judge` compares `got` with: the replay from the seed up to the
    window's opening, and the replay of the steps after the window from
    what the window left (`got["close"]`)."""
    at_open = replay(ref.zero_accounts(dep.n_accounts), empty_queues(dep), 0,
                     int(got["open"]["steps"]), tellers, traffic, dep)
    close = got["close"]
    queues, _ = queues_of(close, dep)
    after = replay(accounts_of(close, dep), queues, int(close["steps"]),
                   int(got["after"]["steps"]) - int(close["steps"]),
                   tellers, traffic, dep)
    return {"open": at_open, "after": after}


def judge(tellers: dict, traffic: dict, dep: Deployment, got: dict,
          limits: dict, expected=None) -> dict:
    """Compare what the timed path left behind with the reference.

    `got`: three readings of the system, `open` (after the warm chunks),
    `close` (what the window left) and `after` (one more chunk through the
    same executable), each with `state` (column -> the whole column, by
    row), the inbox as the system holds it (a block a chip), `steps`, and
    `counters`: the device's own `mail_dropped` (what the spill regions
    could not hold), `exchange_dropped`, `spilled`, `spill_high_water`,
    `exchange_high_water`, summed or maxed over the chips as the system
    reads them.

    1. From the seed: `accounts_wrong_at_open`, `queue_wrong_at_open`: every
       chip's queue row for row, and what the replay itself counted on the
       way (`spilled`, both high waters) against the device's counters.
    2. The transition, from what the window left: `accounts_wrong`,
       `queue_wrong` (with `spilled`'s growth over the chunk against the
       replay's), `tellers_wrong`.
    3. Across the window, from the device's own counters: `ledger_wrong`,
       `negative_balances`; `messages_unaccounted`: between two readings
       the commands sent (steps x tellers) against the growth of `applied`,
       of the queues and of what the counters say was lost; `spilled`'s
       growth between them no smaller than what the spill regions hold at
       the later one and no larger than its steps could have carried at the
       high water; and at every reading no spill region and no pair chunk
       fuller than its high water says it ever was. `messages_dropped`, and
       the exchange's part of it alone (`exchange_dropped`: the sixth
       guarantee)."""
    expected = expected or expect(tellers, traffic, dep, got)
    numbers = {}
    for name, when in (("_at_open", "open"), ("", "after")):
        accounts, queues, _ = expected[when]
        have, malformed = queues_of(got[when], dep)
        numbers["accounts_wrong" + name] = ref.accounts_wrong(
            accounts_of(got[when], dep), accounts)
        numbers["queue_wrong" + name] = sum(
            ref.queue_wrong(h, m, w)
            for h, m, w in zip(have, malformed, queues))
    numbers["queue_wrong"] += sum(queues_of(got["close"], dep)[1])
    changed = np.zeros(dep.n_tellers, bool)
    for k in TELLER_COLUMNS:
        changed |= np.asarray(got["after"]["state"][k],
                              np.int64)[dep.teller_row] != tellers[k]
    numbers["tellers_wrong"] = int(changed.sum())

    ledger = negative = unaccounted = 0
    readings = [got[when] for when in ("open", "close", "after")]
    for snap in readings[1:]:
        acc = accounts_of(snap, dep)
        ledger += int(((acc["balance"] - acc["deposited"] + acc["withdrawn"])
                       % ref.WRAP != 0).sum())
        negative += int((acc["balance"] < 0).sum())
    at_open, over_last = expected["open"][2], expected["after"][2]
    numbers["queue_wrong_at_open"] += sum(
        abs(int(got["open"]["counters"][k]) - at_open[k]) for k in COUNTERS)
    numbers["queue_wrong"] += abs(_spilled_between(
        got["close"], got["after"]) - over_last["spilled"])
    for a, b in zip(readings, readings[1:]):
        steps = int(b["steps"]) - int(a["steps"])
        unaccounted += abs(steps * dep.n_tellers
                           - (_held(b, dep) - _held(a, dep)))
        spilled, high = _spilled_between(a, b), \
            int(b["counters"]["spill_high_water"])
        unaccounted += max(0, int(_regions(b, dep)[0].sum()) - spilled) \
            + max(0, spilled - steps * dep.chips * high)
    for snap in readings:
        spill, chunks = _regions(snap, dep)
        unaccounted += max(0, int(spill.max())
                           - int(snap["counters"]["spill_high_water"]))
        unaccounted += max(0, int(chunks.max())
                           - int(snap["counters"]["exchange_high_water"]))
    last = got["after"]["counters"]
    numbers.update(ledger_wrong=ledger, negative_balances=negative,
                   messages_unaccounted=unaccounted,
                   messages_dropped=_lost(got["after"]),
                   exchange_dropped=int(last["exchange_dropped"]))
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
