"""The slots extent (ops/segment.py `deliver_slots`, StepCore.slots_extent):
the wide slots kernel fills the slots of the rows below an extent R only,
where the host put every slots behavior, and the behavior switch runs the
slots behaviors' folds on those rows only (`StepCore.update`); both put the
N-wide form back for a step in which a live row at or past R takes slots.
The kernel with an extent answers to the literal queue in
tests/test_slots_delivery.py. Here, at toy size, with the extent's tile
made small (`StepCore.EXTENT_ROWS`) so that R < N:

- the bank (`build_bank`, `build_bank_sharded` on 1, 2 and 4 devices) and
  the bank transfer (`build_bank_transfer`: replies, stash replay,
  retention) with R < N, against the same system with R = N, row for row:
  state and stashes, inbox and spill region, counters;
- the fallback: a slots behavior at a row past R after the first compile,
  by `spawn_block` and by a device `_become`, against R = N; a dead or a
  suspended row past R does not trip it;
- where R = N (an all-slots system, a reduce system) nothing is added, and
  the program is the one lowered before the switch was split;
- `host_stats()["slots_extent"]` at the benchmark cells' own sizes."""

import dataclasses
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from akka_tpu.batched import BatchedSystem
from akka_tpu.batched.behavior import Emit, behavior
from akka_tpu.batched.step import StepCore
from akka_tpu.models import baseline_benches as bb
from benchmark.harness import BENCH, load_json

WIDE = "reference"  # the wide slots family, a TPU's `auto`
TILE = 64           # the extent's tile at toy size
A, T, S, SPILL = 96, 288, 4, 512  # tests/test_bank_deployment.py's bank


@pytest.fixture
def small_tile(monkeypatch):
    monkeypatch.setattr(StepCore, "EXTENT_ROWS", TILE)


def no_extent(monkeypatch):
    """Every extent rounds up to n_local: the system runs R = N."""
    monkeypatch.setattr(StepCore, "EXTENT_ROWS", 1 << 30)


# ------------------------------------------------------------------ systems
def carry(system):
    """Everything a run leaves in a system, by name."""
    out = {f"state.{k}": np.asarray(v) for k, v in system.state.items()}
    for k in ("behavior_id", "alive", "inbox_dst", "inbox_type",
              "inbox_payload", "inbox_valid", "step_count", "mail_dropped",
              "spill_stats", "dropped", "mesh_stats", "sup_counts"):
        v = getattr(system, k, None)
        if v is not None:
            out[k] = np.asarray(v)
    return out


def assert_same(a, b):
    assert a.keys() == b.keys()
    for k in a:
        assert (a[k] == b[k]).all(), k


def both_widths(build, monkeypatch, drive):
    """`drive(system)` on the system `build()` makes with R < N, and on the
    same with R = N: what each left after every `yield` of the drive is
    the same. Returns both systems and what the last `yield` left."""
    monkeypatch.setattr(StepCore, "EXTENT_ROWS", TILE)
    narrow = build()
    left = [carry(narrow) for _ in drive(narrow)]
    no_extent(monkeypatch)
    wide = build()
    right = [carry(wide) for _ in drive(wide)]
    assert len(left) == len(right)
    for a, b in zip(left, right):
        assert_same(a, b)
    return narrow, wide, left[-1]


def steps_and_runs(system):
    for _ in range(3):
        system.step()
        yield
    for _ in range(3):
        system.run(8)
        yield


def test_bank_with_an_extent_is_the_bank_without(monkeypatch):
    from benchmark.reference import bank

    traffic = load_json(BENCH, "traffic", "bank-commands.json")
    tellers = bank.seed_tellers(T, A, 2 ** 31 + 23, traffic)

    def build():
        return bb.build_bank(n_tellers=T, n_accounts=A, mailbox_slots=S,
                             spill_capacity=SPILL, tellers=tellers,
                             delivery_backend=WIDE)

    narrow, wide, _ = both_widths(build, monkeypatch, steps_and_runs)
    assert narrow.host_stats()["slots_extent"] == 128  # A up to 64s
    assert wide.host_stats()["slots_extent"] == A + T
    spilled, high = narrow.read_spill()
    assert spilled > 0 and high > 1  # the spill region was used
    accounts, _, _ = bank.replay(bank.zero_accounts(A), bank.empty_queue(),
                                 0, 27, tellers, traffic, S)
    for k in bank.ACCOUNT_COLUMNS:
        assert (narrow.read_state(k)[:A] == accounts[k]).all(), k


@pytest.mark.parametrize("chips", [1, 2, 4])
def test_sharded_bank_with_an_extent_is_the_bank_without(chips,
                                                         monkeypatch):
    from benchmark.reference import bank_sharded as ref

    # tests/test_bank_sharded_deployment.py's toy: 8 logical shards
    xa, xt = 256, 512
    traffic = load_json(BENCH, "traffic", "bank-commands-mesh.json")
    tellers = ref.seed_tellers(xt, xa, 2 ** 31 + 41, traffic)

    def build():
        return bb.build_bank_sharded(
            n_tellers=xt, n_accounts=xa, n_devices=chips, n_shards=8,
            mailbox_slots=S, spill_capacity=64, tellers=tellers,
            delivery_backend=WIDE)

    def drive(system):
        for k in (1, 1, 1, 8, 8):
            system.run(k)
            yield

    narrow, wide, _ = both_widths(build, monkeypatch, drive)
    assert narrow.host_stats()["slots_extent"] == xa // chips
    assert wide.host_stats()["slots_extent"] == (xa + xt) // chips
    assert narrow.read_spill()[0] > 0


@pytest.mark.parametrize("family", [WIDE, "xla"])
def test_bank_transfer_with_an_extent_is_the_transfer_without(family,
                                                              monkeypatch):
    """Accounts that answer, stash and replay under a budget whose
    leftovers are retained: the folds over rows [0, R) and the tellers'
    switch over the rest give the N-wide switch's state, stashes,
    emissions (the next inbox), spill and counters."""
    from benchmark.reference import bank_transfer as ref

    a = 512  # tests/test_bank_transfer.py's toy
    keys = ref.seed_keys(a, 2 ** 31 + 43)

    def build():
        return bb.build_bank_transfer(
            n_accounts=a, n_tellers=a, mailbox_slots=16, emit_width=4,
            stash_capacity=64, spill_capacity=512, keys=keys,
            delivery_backend=family)

    def drive(system):
        for k in (1, 1, 8, 8, 8, 8):  # two programs a width
            system.run(k)
            yield

    narrow, wide, _ = both_widths(build, monkeypatch, drive)
    assert narrow.host_stats()["slots_extent"] == a < narrow.capacity
    assert wide.host_stats()["slots_extent"] == 2 * a
    counters = narrow.read_emit_stats()
    assert counters["stashed"] > 0 and counters["retained_by_budget"] > 0
    assert counters == wide.read_emit_stats()


# ---------------------------------------------------------------- fallback
SLEEPER_SPEC = {"_become": ((), jnp.int32), "woken": ((), jnp.int32)}


@behavior("sleeper", SLEEPER_SPEC)
def sleeper(state, inbox, ctx):
    """A reduce row that becomes an account (behavior 0) on its first
    mail."""
    return ({"_become": jnp.where(inbox.count > 0, 0, -1),
             "woken": state["woken"] + inbox.count}, Emit.none(1, bb.PAYLOAD_W))


def spare_bank(n_accounts=96, n_sleepers=160, spare=128):
    """Accounts first, then sleepers, then `spare` unspawned rows: R is
    the accounts' rows rounded up to the tile, N the whole capacity."""
    system = BatchedSystem(capacity=n_accounts + n_sleepers + spare,
                           behaviors=[bb.bank_account, sleeper],
                           payload_width=bb.PAYLOAD_W, host_inbox=256,
                           mailbox_slots=S, spill_capacity=256,
                           delivery_backend=WIDE)
    system.spawn_block(bb.bank_account, n_accounts)
    system.spawn_block(sleeper, n_sleepers)
    return system


def commands(system, rows, step):
    """Two deposits and a withdrawal a row, the sender's row in column 1:
    an account that applied them in another order, or applied slots it
    was not sent, reads otherwise."""
    rows = np.asarray(rows)
    for j, (kind, amount) in enumerate(((1, 5), (2, 7), (1, 3))):
        pl = np.zeros((rows.size, bb.PAYLOAD_W), np.float32)
        pl[:, 0] = amount + step
        pl[:, 1] = 1000 + 10 * step + j
        system.tell(rows, pl, mtype=kind)


def test_spawned_past_the_extent_after_the_compile(monkeypatch):
    def drive(system):
        for t in range(3):
            commands(system, np.arange(0, 96, 5), t)
            system.run(2)  # compiles with R = 128 (narrow)
            yield
        new = system.spawn_block(bb.bank_account, 40)  # rows 256..295
        assert new.min() >= 2 * TILE
        for t in range(3, 6):
            commands(system, np.concatenate([new, [3, 17]]), t)
            system.run(2)  # the same program: the guard puts N back
            yield

    narrow, _, last = both_widths(spare_bank, monkeypatch, drive)
    assert narrow.host_stats()["slots_extent"] == 2 * TILE  # as compiled
    assert (last["state.applied"][256:296] == 9).all()


def test_become_past_the_extent_on_the_device(monkeypatch):
    def drive(system):
        commands(system, [5], 0)
        system.run(1)
        yield
        woken = [100, 150, 255]  # sleepers, all past R = 128
        commands(system, woken, 1)  # the mail wakes them: become account
        system.run(1)
        yield
        assert (np.asarray(system.behavior_id)[woken] == 0).all()
        for t in range(2, 5):
            commands(system, woken + [5], t)
            system.run(1)
            yield

    narrow, _, last = both_widths(spare_bank, monkeypatch, drive)
    assert narrow.host_stats()["slots_extent"] == 2 * TILE
    assert (last["state.applied"][[100, 150, 255]] == 9).all()
    assert (last["state.woken"][[100, 150, 255]] == 3).all()


def test_a_dead_row_past_the_extent_does_not_put_the_width_back(small_tile):
    """Unspawned rows hold behavior 0, the account, a slots behavior: not
    alive, they do not trip the guard; a live one does."""
    system = spare_bank()
    core = system._core
    assert core.slots_extent == 2 * TILE < system.capacity
    guard = jax.jit(lambda b, a: core.beyond_extent(b, a)[1])
    bid, alive = system.behavior_id, system.alive
    assert int(np.asarray(bid)[300]) == 0 and not bool(alive[300])
    assert not bool(guard(bid, alive))
    assert bool(guard(bid, alive.at[300].set(True)))
    assert not bool(guard(bid.at[300].set(1), alive.at[300].set(True)))


def test_a_suspended_row_past_the_extent_does_not_put_the_width_back(
        monkeypatch):
    """A row whose receive failed is suspended: alive, its `_failed` flag
    up, its receive not run, its mail retained. An account so suspended
    past R does not trip the guard, and the split update gives what the
    N-wide one gives while its mail waits in the spill region."""
    guarded = dataclasses.replace(sleeper, name="guarded",
                                  nonfinite_guard=True)  # a `_failed` column
    row = 200

    def build():
        system = BatchedSystem(capacity=384,
                               behaviors=[bb.bank_account, guarded],
                               payload_width=bb.PAYLOAD_W, host_inbox=256,
                               mailbox_slots=S, spill_capacity=256,
                               delivery_backend=WIDE)
        system.spawn_block(bb.bank_account, 96)
        system.spawn_block(guarded, 160)
        system.behavior_id = system.behavior_id.at[row].set(0)
        system.state = dict(system.state,
                            _failed=system.state["_failed"].at[row].set(True))
        return system

    def drive(system):
        for t in range(3):
            commands(system, [5, row], t)
            system.run(2)
            yield

    narrow, _, last = both_widths(build, monkeypatch, drive)
    monkeypatch.setattr(StepCore, "EXTENT_ROWS", TILE)
    core = narrow._core
    assert core.slots_extent == 2 * TILE <= row
    assert last["state.applied"][5] == 9 and last["state.applied"][row] == 0
    assert narrow.read_spill()[1] >= 9  # the suspended row's mail waits
    guard = jax.jit(lambda b, a, f: core.beyond_extent(b, a, None, f)[1])
    bid, alive, failed = (narrow.behavior_id, narrow.alive,
                          narrow.state["_failed"])
    assert bool(alive[row]) and bool(failed[row])
    assert not bool(guard(bid, alive, failed))
    assert bool(guard(bid, alive, failed.at[row].set(False)))


# ------------------------------------------------------- where R = N
def test_all_slots_and_reduce_systems_take_no_extent(small_tile,
                                                    monkeypatch):
    """Their step programs are the ones traced with the extent's code
    taken out: no guard, no cond, operation for operation."""
    def build():
        every = BatchedSystem(capacity=256, behaviors=[bb.bank_account],
                              payload_width=bb.PAYLOAD_W, host_inbox=8,
                              mailbox_slots=S, spill_capacity=64,
                              delivery_backend=WIDE)
        every.spawn_block(bb.bank_account, 256)
        return [(every, 256), (bb.build_ring(n=256, static=False), None)]

    def text(system):
        return system._run_jit.lower(*system._carry(), 2,
                                     system._topo_arrays).as_text()

    with_extent = []
    for system, extent in build():
        with_extent.append(text(system))
        assert "place_all" not in with_extent[-1]
        assert system.host_stats()["slots_extent"] == extent
    monkeypatch.setattr(StepCore, "beyond_extent",
                        lambda self, *a: (None, None))
    assert [text(system) for system, _ in build()] == with_extent


# sha256 of the lowered step programs (no source locations in the text)
# as the code before the split switch lowers them: where R = N the update
# adds nothing, operation for operation. A change to the slots enqueue on
# purpose (the single routing of `_deliver_slots_wide`) updates the two
# slots systems' numbers; the ring's stays
UNSPLIT = {
    "all-slots": "86e6e5ace8955bbcb22c9f485b06ea244216c6da180db49038c1fa20c5f5c528",
    "all-answering": "294d540859d476e34c5441c07f5772047f3e99914171d1a34b1cf31dfa7ea25c",
    "ring": "b93c8f044f8471ec3d5c3b2768a5c5aa723c235c183621622dbbb0b38d5d4de7",
}


@pytest.mark.parametrize("name", sorted(UNSPLIT))
def test_all_slots_and_reduce_systems_lower_the_unsplit_program(name,
                                                                small_tile):
    def every(b):
        system = BatchedSystem(capacity=256, behaviors=[b],
                               payload_width=bb.PAYLOAD_W, host_inbox=8,
                               mailbox_slots=S, spill_capacity=64,
                               delivery_backend=WIDE)
        system.spawn_block(b, 256)
        return system

    system = {"all-slots": lambda: every(bb.bank_account),
              "all-answering": lambda: every(bb.make_transfer_account(8, 2)),
              "ring": lambda: bb.build_ring(n=256, static=False)}[name]()
    jax.clear_caches()  # the text as a fresh process lowers it
    text = system._run_jit.lower(*system._carry(), 2,
                                 system._topo_arrays).as_text()
    assert "loc(" not in text and ".all" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == UNSPLIT[name]


def test_extent_at_the_cells_sizes():
    """`bank-ordered-1m`: 131,072 accounts first in 1,179,648 rows;
    `xshard-bank-4chip`: a chip's 32,768 accounts first in its 294,912."""
    one = bb.build_bank()
    assert one._core.slots_extent == 131072 < one.capacity
    mesh = bb.build_bank_sharded(n_devices=4, spill_capacity=4096,
                                 remote_capacity_per_pair=73728)
    assert mesh._core.slots_extent == 32768 < mesh.local_n == 294912
    one._run_jit.lower(*one._carry(), 1, one._topo_arrays)
    assert one.host_stats()["slots_extent"] == 131072
