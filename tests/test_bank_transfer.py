"""Savina's Bank Transaction as a deployment (`build_bank_transfer`): accounts
that answer every message, stash while a transfer is outstanding and replay
the stash ahead of newer mail, under an emission budget whose leftovers are
retained through the spill region. Against the plain reference
(benchmark/reference/bank_transfer.py) chunk by chunk on both slots
families; the emitting fold, `unstashAll`'s order and retention's order at
a row's scale; the counters and their readers; what the path refuses; and
the two bank programs, which the emitting path leaves as they were."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_tpu.batched import (BatchedSystem, Emit, Mailbox, StashBuffer,
                              behavior)
from akka_tpu.batched.step import StepCore
from akka_tpu.models import baseline_benches as bb
from benchmark.reference import bank_transfer as ref

# 512 accounts, as many tellers: ~300 tells a step, stashes of 4 and more
# within ten steps, and steps at which the budget leaves mail retained
A, S, K, C, SPILL = 512, 16, 4, 64, 512
FAMILIES = ["xla", "reference"]  # ranked (a CPU's `auto`), wide (a TPU's)


def build(family, seed=2 ** 31 + 41):
    keys = ref.seed_keys(A, seed)
    system = bb.build_bank_transfer(
        n_accounts=A, n_tellers=A, mailbox_slots=S, emit_width=K,
        stash_capacity=C, spill_capacity=SPILL, keys=keys,
        delivery_backend=family)
    return keys, system


@pytest.mark.parametrize("family", FAMILIES)
def test_chunks_against_the_reference(family):
    keys, system = build(family)
    rule = ref.Rule(A, S, K, C, SPILL)
    world = ref.World.start(keys, A)
    assert system.inbox_dst.shape[0] == SPILL + A * K + A + 8
    total = {"stashed": 0, "retained": 0, "replayed": 0}
    for _ in range(10):
        system.run(4)
        drew = ref.replay(world, rule, 4)
        for k in total:
            total[k] += drew[k]
        got = bb.transfer_left_behind(system, A)
        assert got["steps"] == world.t
        assert ref.compare(got, world, keys) == dict.fromkeys(
            ("accounts_wrong", "tellers_wrong", "stashes_wrong",
             "queue_wrong"), 0), world.t
        assert ref.ledger(got) == 0 and ref.unaccounted(got) == 0
    counters = system.read_emit_stats()
    assert counters["stashed"] == total["stashed"] > 0
    assert counters["retained_by_budget"] == total["retained"] > 0
    assert counters["stash_high_water"] >= 4
    assert counters["emit_high_water"] == K  # a row filled its budget
    assert counters["stash_overflow"] == 0 and got["dropped"] == 0
    # stashes were replayed, and every transfer's chain ran to its teller
    assert total["replayed"] > 0 and int(got["tellers"]["done"].sum()) > A
    assert system.host_stats()["stashed"] == counters["stashed"]


def test_the_transfer_rule_is_the_references():
    keys = ref.seed_keys(257, 5)
    ks = np.arange(257) * 7 % 300
    want = ref.transfers(keys, ks, 131072)
    got = jax.jit(lambda key, k: bb.transfer_of(
        key.view(jnp.uint32), k, 131072, (131072 // 10) * 8))(
            jnp.asarray(keys), jnp.asarray(ks, jnp.int32))
    for w, g in zip(want, got):
        assert (np.asarray(g, np.int64) == w).all()
    src, dst, amount = want
    assert (src < 104856).all() and (dst > src).all() and (dst < 131072).all()
    assert amount.min() >= 1 and amount.max() <= 1000


# ------------------------------------------------ the fold at a row's scale
ECHO, HOLD, OPEN = 1, 2, 3  # answer; stash from now on; open the stash


def echo_fold(mailbox, stash, budget):
    """A row that answers ECHO(v) with v to row 9, stashes while shut
    (HOLD shuts it, OPEN opens it) and logs every value it handles."""
    def fn(carry, kind, pl):
        shut, log, n = carry
        v = pl[0].astype(jnp.int32)
        handled = (kind == ECHO)
        new = (jnp.where(kind == HOLD, 1, jnp.where(kind == OPEN, 0, shut)),
               log.at[n].set(jnp.where(handled, v, -1)), n + 1)
        emit = Emit.single(9, pl, 1, 2, when=handled)
        return new, emit, (shut == 1) & (kind == ECHO)

    box = mailbox._replace(budget=budget)
    return box.fold_emit((jnp.int32(0), jnp.full((24,), -9, jnp.int32),
                          jnp.int32(0)), fn, stash,
                         is_open=lambda c: c[0] == 0)


def mailbox_of(msgs, slots=8):
    types = jnp.zeros((slots,), jnp.int32).at[:len(msgs)].set(
        jnp.asarray([m[0] for m in msgs], jnp.int32))
    payload = jnp.zeros((slots, 2), jnp.float32).at[:len(msgs), 0].set(
        jnp.asarray([m[1] for m in msgs], jnp.float32))
    valid = jnp.arange(slots) < len(msgs)
    return Mailbox(types=types, payload=payload, valid=valid,
                   count=jnp.int32(len(msgs)), sum=jnp.zeros((2,)),
                   max=jnp.zeros((2,)))


def empty_stash(cap=6):
    return StashBuffer(jnp.zeros((cap,), jnp.int32),
                       jnp.zeros((cap, 2), jnp.float32), jnp.int32(0),
                       jnp.int32(0))


def held(stash):
    """A stash's messages' values, oldest first."""
    cap = stash.types.shape[0]
    at = (int(stash.head) + np.arange(int(stash.size))) % cap
    return [float(stash.payload[j, 0]) for j in at]


def test_fold_emits_in_processing_order_and_stops_at_the_budget():
    box = mailbox_of([(ECHO, 1), (ECHO, 2), (ECHO, 3), (ECHO, 4)])
    carry, stash, out = echo_fold(box, empty_stash(), budget=3)
    sent = np.asarray(out.emit.valid)
    assert list(np.asarray(out.emit.payload)[sent, 0]) == [1, 2, 3]
    assert (np.asarray(out.emit.dst)[sent] == 9).all()
    assert int(out.taken) == 3  # the 4th would pass the budget: retained
    assert int(stash.size) == 0 and int(out.stashed) == 0


def test_unstash_all_replays_the_stash_in_order_ahead_of_newer_mail():
    # shut: 5, 6, 7 stashed; OPEN: the stash replays 5, 6, 7, then 8
    box = mailbox_of([(HOLD, 0), (ECHO, 5), (ECHO, 6), (ECHO, 7), (OPEN, 0),
                      (ECHO, 8)])
    carry, stash, out = echo_fold(box, empty_stash(), budget=8)
    sent = np.asarray(out.emit.valid)
    assert list(np.asarray(out.emit.payload)[sent, 0]) == [5, 6, 7, 8]
    assert int(out.stashed) == 3 and int(stash.size) == 0
    assert int(out.taken) == 6


def test_a_budget_leaves_the_stash_its_tail_and_a_full_stash_counts():
    # an old stash [1, 2], open: replays 1, 2 then budget 3 takes mail 3;
    # mail 4 is retained
    # kept in its ring from position 3 on: 1 at 3, 2 wrapped round to 0
    old = StashBuffer(jnp.asarray([ECHO, 0, 0, ECHO], jnp.int32),
                      jnp.asarray([[2, 0], [0, 0], [0, 0], [1, 0]],
                                  jnp.float32), jnp.int32(3), jnp.int32(2))
    carry, stash, out = echo_fold(mailbox_of([(ECHO, 3), (ECHO, 4)]), old,
                                  budget=3)
    sent = np.asarray(out.emit.valid)
    assert list(np.asarray(out.emit.payload)[sent, 0]) == [1, 2, 3]
    assert int(out.taken) == 1 and int(stash.size) == 0
    # budget 1: replays 1, keeps [2], takes no mail
    carry, stash, out = echo_fold(mailbox_of([(ECHO, 3)]), old, budget=1)
    assert int(out.taken) == 0 and held(stash) == [2]
    # shut, a stash of capacity 2: 7 and 8 fit, 9 is lost; OPEN replays
    box = mailbox_of([(HOLD, 0), (ECHO, 7), (ECHO, 8), (ECHO, 9), (OPEN, 0)])
    carry, stash, out = echo_fold(box, empty_stash(2), budget=2)
    sent = np.asarray(out.emit.valid)
    assert list(np.asarray(out.emit.payload)[sent, 0]) == [7, 8]
    assert int(out.stashed) == 2 and int(out.lost) == 1
    assert int(stash.size) == 0 and int(out.taken) == 5


# ------------------------------------------------------- retention's order
@behavior("logger", {"log": ((8,), jnp.int32), "n": ((), jnp.int32)},
          inbox="slots", emit_width=1, always_on=True)
def one_a_step(state, mailbox, ctx):
    """Answers every message to row 1 and logs its value; the budget of 1
    lets one message through a step."""
    def fn(carry, kind, pl):
        log, n = carry
        return ((log.at[n].set(pl[0].astype(jnp.int32)), n + 1),
                Emit.single(1, pl, 1, 2, when=True))

    (log, n), _, out = mailbox.fold_emit((state["log"], state["n"]), fn)
    return {"log": log, "n": n}, out


@behavior("sink", {"got": ((), jnp.int32)})
def sink(state, inbox, ctx):
    return {"got": state["got"] + inbox.count}, Emit.none(1, 2)


@pytest.mark.parametrize("family", FAMILIES)
def test_retained_slots_precede_overflow_and_fresh_mail(family):
    system = BatchedSystem(capacity=2, behaviors=[one_a_step, sink],
                           payload_width=2, host_inbox=8, mailbox_slots=2,
                           spill_capacity=8, delivery_backend=family,
                           emit_rows=1, metrics_enabled=True)
    system.spawn_block(one_a_step, 1)
    system.spawn_block(sink, 1)
    system.tell(np.zeros(3, np.int32), np.asarray([[1, 0], [2, 0], [3, 0]]))
    system.run(1)  # takes 1; 2 retained, 3 past the slots
    system.tell([0], np.asarray([[4, 0]]))
    system.run(5)
    log = system.read_state("log")[0]
    assert list(log[:4]) == [1, 2, 3, 4]
    assert int(system.read_state("got")[1]) == 4
    stats = system.read_emit_stats()
    assert stats["retained_by_budget"] >= 2 and stats["emit_high_water"] == 1
    _, lanes = system.drain_metrics()  # the registry's drain carries them
    assert {k: int(lanes[k][0]) for k in stats} == stats


# ------------------------------------------------- counters and refusals
def test_counters_ride_checkpoint_and_restore(tmp_path):
    keys, system = build("xla")
    system.run(12)
    before = system.read_emit_stats()
    assert before["stashed"] > 0 and set(before) == set(
        ("stashed", "stash_high_water", "stash_overflow",
         "retained_by_budget", "emit_high_water"))
    path = system.checkpoint(str(tmp_path))
    _, other = build("xla")
    other.restore(path)
    assert other.read_emit_stats() == before
    assert (other.read_state("stash_size") == system.read_state(
        "stash_size")).all()
    system.run(4)
    other.run(4)
    assert other.read_emit_stats() == system.read_emit_stats()


def test_what_the_emitting_path_refuses():
    account = bb.make_transfer_account(8, 2)
    with pytest.raises(ValueError, match="spill region"):
        BatchedSystem(capacity=8, behaviors=[account], payload_width=4,
                      mailbox_slots=4, spill_capacity=0)
    with pytest.raises(ValueError, match="wider than the emission width"):
        BatchedSystem(capacity=8, behaviors=[account], payload_width=4,
                      mailbox_slots=4, out_degree=3)
    system = BatchedSystem(capacity=8, behaviors=[account], payload_width=4,
                           mailbox_slots=4, emit_rows=4)
    with pytest.raises(ValueError, match="emit_rows"):
        system.spawn_block(account, 6)
    assert system.free_row_count == 8
    # the mesh runs an emission width, and refuses what BatchedSystem
    # refuses: no spill region, and an answering row past a chip's
    # emit_rows
    from akka_tpu.batched.sharded import ShardedBatchedSystem
    with pytest.raises(ValueError, match="spill region"):
        ShardedBatchedSystem(capacity=8, behaviors=[account], n_devices=2,
                             payload_width=4, mailbox_slots=4,
                             spill_capacity=0)
    mesh = ShardedBatchedSystem(capacity=8, behaviors=[account], n_devices=2,
                                payload_width=4, mailbox_slots=4,
                                emit_rows=2)
    assert mesh.emission_rows == 2 * 2 + 2 * 1
    with pytest.raises(ValueError, match="emit_rows"):
        mesh.spawn_block(account, 3)
    assert mesh._next_row == 0  # the rows given back


# ------------------------------------- the other cells' programs unchanged
# sha256 of the lowered step programs (the text carries no source
# locations) of the six other cells' deployments at a toy size, as the
# parent of the emitting path lowers them: that path is compiled in only
# where a behavior declares an emission width, so these programs are the
# same, operation for operation. The "-extent" four are the slots cells'
# programs on the wide family with the extent's tile made small, so that
# R < N as at the cells' own sizes (`transfer-mesh-extent`, the answering
# path behind the exchange, is `xshard-transfer-4chip`'s). A change to one
# of those steps on purpose updates its number.
PROGRAMS = {
    "bank-auto": "432be1b0116a4e063cebcb47e7fa712562fc86cfd6a15aa65a7bdcafad103864",
    "bank-wide": "dbae48e4c31361f3f8ee0b53c9e2d5239d479b27f69de2adc1853bf357ed9871",
    "bank-mesh": "5b9c667195f3ce21bb7b0a601681fb975407df510695e2b56eb6d988fffbff6e",
    "ring": "c9de7d7f6b1707cd46c1d2455590699cce4a5e6a2856e5ddfadf017baf68a170",
    "ring-mesh": "123cbc1498d053eaf60f681f93b5748d92e5fffff0327d1809445ddcb4c646e7",
    "fanin": "e2156312d6e7b569adb583d7cc81d2f8379a1c07a16380467b2a2d02bbffd9ef",
    "router": "bfdcf1b78da35abd7570e3f703a04110747ff80b29c6547a5a3463ed382db7c1",
    "bank-extent": "12d9243b1d13827c5628d86561826c3ed669733f92b4b80f98ad5213bc2ee1bf",
    "bank-mesh-extent": "31615cd516a9cb5fe4ad9ba2882e37aee0b6152d744cf91a5fb8acc6da24b61e",
    "transfer-extent": "407e268fe8dd336f0b2c88b3c8fce96dbea8a4d203361f0b1ffc15e5edeafbc7",
    "transfer-mesh-extent": "fa24303c626ff0c28719c4f0e0f845509d55181ac8455991741afa1d72dab6a6",
}


def _lowered_with_an_extent(name):
    wide = dict(mailbox_slots=4, delivery_backend="reference")
    if name == "transfer-mesh-extent":
        s = bb.build_bank_transfer_sharded(
            n_accounts=256, n_tellers=256, n_shards=8, n_devices=4,
            mailbox_slots=16, emit_width=4, stash_capacity=64,
            spill_capacity=128, remote_capacity_per_pair=96,
            delivery_backend="reference")
        text = s._build_step(s.stray_mode).lower(
            *s._carry(), s.tables, 2, *s._kept_stats()).as_text()
        assert s._core.compiled_extent == 64 < s.local_n
        return text
    if name == "bank-mesh-extent":
        s = bb.build_bank_sharded(n_tellers=512, n_accounts=256, n_shards=8,
                                  n_devices=4, spill_capacity=256,
                                  remote_capacity_per_pair=96, **wide)
        text = s._build_step(s.stray_mode).lower(
            *s._carry(), s.tables, 2, *s._kept_stats()).as_text()
        assert s._core.compiled_extent == 64 < s.local_n
        return text
    s = (bb.build_bank(n_tellers=512, n_accounts=256, spill_capacity=256,
                       **wide) if name == "bank-extent"
         else bb.build_bank_transfer(
            n_accounts=256, n_tellers=256, mailbox_slots=16, emit_width=4,
            stash_capacity=64, spill_capacity=512,
            delivery_backend="reference"))
    text = s._run_jit.lower(*s._carry(), 2, s._topo_arrays).as_text()
    assert s._core.compiled_extent == 256 < s.capacity
    return text


def _lowered(name):
    # how many private copies of a jitted helper (`_where`) the text holds
    # depends on what JAX's caches kept from earlier traces in the process:
    # lower from empty caches, as a fresh process does
    jax.clear_caches()
    if name.endswith("-extent"):
        return _lowered_with_an_extent(name)
    if name.endswith("mesh"):
        s = (bb.build_bank_sharded(n_tellers=512, n_accounts=256, n_shards=8,
                                   n_devices=4, mailbox_slots=4,
                                   spill_capacity=256,
                                   remote_capacity_per_pair=96)
             if name == "bank-mesh" else bb.build_cross_shard(8, 64,
                                                              n_devices=4))
        return s._build_step(s.stray_mode).lower(
            *s._carry(), s.tables, 2, *s._kept_stats()).as_text()
    s = {"bank-auto": lambda: bb.build_bank(
            n_tellers=512, n_accounts=256, mailbox_slots=4,
            spill_capacity=256),
         "bank-wide": lambda: bb.build_bank(
            n_tellers=512, n_accounts=256, mailbox_slots=4,
            spill_capacity=256, delivery_backend="reference"),
         "ring": lambda: bb.build_ring(n=1024, static=False),
         "fanin": lambda: bb.build_fan_in(n_leaves=2048, n_collectors=16,
                                          static=False),
         "router": lambda: bb.build_router_pool(n_producers=2048,
                                                n_routees=200)}[name]()
    return s._run_jit.lower(*s._carry(), 2, s._topo_arrays).as_text()


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_the_other_cells_programs_are_the_parents(name, monkeypatch):
    if name.endswith("-extent"):
        monkeypatch.setattr(StepCore, "EXTENT_ROWS", 64)
    text = _lowered(name)
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PROGRAMS[name]


@pytest.mark.parametrize("retain", [False, True])
def test_an_inbox_of_no_rows_on_both_families(retain):
    """The ranked family read a row of an empty inbox (a gather at -1):
    with no rows both families deliver nothing, spill nothing and lose
    nothing, with or without retention."""
    from akka_tpu.ops.segment import deliver_slots
    out = []
    for family in FAMILIES:
        d, _, retain_fn = deliver_slots(
            jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
            jnp.zeros((0, 4)), jnp.zeros((0,), bool), 8, 4, spill_cap=4,
            slots_kind=jnp.ones((8,), bool), backend=family, retain=retain)
        if retain:
            (sd, _, _, sv), lost, kept = retain_fn(jnp.zeros((8,), jnp.int32))
        else:
            sd, sv, lost, kept = d.spill_dst, d.spill_valid, d.dropped, 0
        assert not np.asarray(d.valid).any() and not np.asarray(sv).any()
        assert int(lost) == 0 and int(kept) == 0
        out.append(np.asarray(d.count))
    assert (out[0] == out[1]).all() and not out[0].any()
