"""Per-message device mailboxes: ordered slot delivery + non-commutative
behaviors (VERDICT r1 item 1).

The reference contract being matched: a mailbox is a queue of discrete
envelopes processed in per-sender FIFO order
(dispatch/Mailbox.scala:260-277). Here that becomes stable (recipient, seq)
sorted delivery into per-actor mailbox slots, and these tests pin the
ordering guarantee against a host oracle that replays the same messages
sequentially — including the bank-account behavior the round-1 verdict named
as the done-criterion.
"""

import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from akka_tpu.batched import BatchedSystem, Emit, Mailbox, behavior
from akka_tpu.ops import segment as sg

F32 = jnp.float32
I32 = jnp.int32

# bank ops (message types)
DEPOSIT, WITHDRAW, SET = 0, 1, 2


def bank_oracle(n, dst, mtype, amount):
    """Sequential replay in (recipient, arrival) order — the host-runtime
    semantics a slot-mode device step must reproduce bit-for-bit."""
    balance = np.zeros(n, np.float32)
    rejected = np.zeros(n, np.int32)
    order = np.argsort(dst, kind="stable")
    for i in order:
        d, t, a = int(dst[i]), int(mtype[i]), float(amount[i])
        if d < 0 or d >= n:
            continue
        if t == DEPOSIT:
            balance[d] += a
        elif t == WITHDRAW:
            if balance[d] >= a:
                balance[d] -= a
            else:
                rejected[d] += 1
        else:  # SET
            balance[d] = a
    return balance, rejected


def make_account(out_degree=1, payload_width=4):
    @behavior("account", {"balance": ((), F32), "rejected": ((), I32)},
              inbox="slots")
    def account(state, mailbox: Mailbox, ctx):
        def apply(carry, t, pl):
            bal, rej = carry
            amt = pl[0]
            can = bal >= amt
            new_bal = jnp.where(
                t == DEPOSIT, bal + amt,
                jnp.where(t == WITHDRAW, jnp.where(can, bal - amt, bal), amt))
            new_rej = rej + jnp.where((t == WITHDRAW) & ~can, 1, 0).astype(I32)
            return (new_bal, new_rej)

        bal, rej = mailbox.fold((state["balance"], state["rejected"]), apply)
        return ({"balance": bal, "rejected": rej},
                Emit.none(out_degree, payload_width))

    return account


def test_bank_account_matches_oracle_host_seeded():
    """Multiple host-seeded messages per actor per step; non-commutative ops
    (withdraw-if-sufficient, set) must apply in arrival order. Overflow past
    the 16 slots SPILLS and redelivers next step in FIFO order (unbounded-
    mailbox default, dispatch/Mailbox.scala:647), so after draining the spill
    the FULL oracle must match with zero losses."""
    rng = np.random.default_rng(7)
    n, m = 257, 2000
    dst = rng.integers(0, n, m).astype(np.int32)
    mtype = rng.integers(0, 3, m).astype(np.int32)
    amount = rng.integers(1, 20, m).astype(np.float32)

    acct = make_account()
    s = BatchedSystem(capacity=n, behaviors=[acct], payload_width=4,
                      out_degree=1, host_inbox=m, mailbox_slots=16,
                      native_staging=False)
    s.spawn_block(acct, n)
    pl = np.zeros((m, 4), np.float32)
    pl[:, 0] = amount
    # seed_inbox writes the first m inbox slots: arrival order = index order
    s.seed_inbox(dst, pl, mtype)
    s.step()
    s.block_until_ready()

    # after ONE step only each recipient's first 16 (in stable (recipient,
    # seq) order) have been consumed — the rest are in the spill, not lost
    keep = np.zeros(m, bool)
    seen = {}
    for i in np.argsort(dst, kind="stable"):
        c = seen.get(int(dst[i]), 0)
        if c < 16:
            keep[i] = True
        seen[int(dst[i])] = c + 1
    bal_exp, rej_exp = bank_oracle(n, dst[keep], mtype[keep], amount[keep])
    np.testing.assert_array_equal(s.read_state("balance"), bal_exp)
    np.testing.assert_array_equal(s.read_state("rejected"), rej_exp)
    assert s.mailbox_overflow == 0  # spilled, not dropped

    # drain the spill: every message eventually applies, in FIFO order
    for _ in range(4):
        s.step()
    s.block_until_ready()
    bal_full, rej_full = bank_oracle(n, dst, mtype, amount)
    np.testing.assert_array_equal(s.read_state("balance"), bal_full)
    np.testing.assert_array_equal(s.read_state("rejected"), rej_full)
    assert s.mailbox_overflow == 0
    assert s.pending_messages == 0


def test_per_sender_fifo_through_device_emissions():
    """Senders emit ordered pairs (SET x then DEPOSIT 1) from their two
    out-slots; the account must apply them in emission order -> balance
    x+1, never x (which a reversed or summed delivery would produce)."""
    n_senders, n_accounts = 64, 8
    total = n_senders + n_accounts

    acct = make_account(out_degree=2)

    @behavior("sender", {"target": ((), I32), "x": ((), F32)}, inbox="slots")
    def sender(state, mailbox: Mailbox, ctx):
        # ping (any message) triggers the ordered pair
        e = Emit.none(2, 4)
        e = Emit(
            dst=e.dst.at[0].set(state["target"]).at[1].set(state["target"]),
            payload=e.payload.at[0, 0].set(state["x"]).at[1, 0].set(1.0),
            valid=e.valid.at[0].set(True).at[1].set(True),
            type=e.type.at[0].set(SET).at[1].set(DEPOSIT),
        )
        return {}, e

    s = BatchedSystem(capacity=total, behaviors=[acct, sender],
                      payload_width=4, out_degree=2, host_inbox=n_senders,
                      mailbox_slots=2 * n_senders // n_accounts,
                      native_staging=False)
    s.spawn_block(acct, n_accounts)
    targets = np.arange(n_senders) % n_accounts
    xs = (10.0 + np.arange(n_senders)).astype(np.float32)
    s.spawn_block(sender, n_senders,
                  init_state={"target": targets.astype(np.int32), "x": xs})
    # trigger every sender
    s.tell(np.arange(n_accounts, total, dtype=np.int32),
           np.zeros(4, np.float32))
    s.step()   # senders emit
    s.step()   # accounts apply
    s.block_until_ready()

    bal = s.read_state("balance")[:n_accounts]
    # oracle: messages sorted by (dst, sender flat slot index) — senders with
    # lower ids sort first; each pair is (SET x, DEPOSIT 1) in order
    exp = np.zeros(n_accounts, np.float32)
    for sid in range(n_senders):  # ascending flat index = delivery order
        t = targets[sid]
        exp[t] = xs[sid]      # SET
        exp[t] += 1.0         # DEPOSIT after its own SET
    np.testing.assert_array_equal(bal, exp)
    assert s.mailbox_overflow == 0


def test_reduce_behavior_runs_inside_slots_system():
    """Mixed system: a commutative counter (inbox='reduce') coexists with
    slot accounts; the counter sees the aggregated view."""
    acct = make_account()

    @behavior("counter", {"total": ((), F32), "n": ((), I32)})
    def counter(state, inbox, ctx):
        return ({"total": state["total"] + inbox.sum[0],
                 "n": state["n"] + inbox.count}, Emit.none(1, 4))

    s = BatchedSystem(capacity=16, behaviors=[acct, counter], payload_width=4,
                      host_inbox=32, mailbox_slots=8, native_staging=False)
    s.spawn_block(acct, 8)
    s.spawn_block(counter, 8)
    pl = np.zeros((6, 4), np.float32)
    pl[:, 0] = [5, 3, 2, 7, 1, 4]
    s.seed_inbox(np.asarray([0, 0, 0, 8, 8, 9]), pl,
                 np.asarray([DEPOSIT, WITHDRAW, DEPOSIT, 0, 0, 0]))
    s.step()
    s.block_until_ready()
    assert s.read_state("balance")[0] == 4.0   # 5 - 3 + 2 in order
    assert s.read_state("total")[8] == 8.0     # 7 + 1 summed
    assert s.read_state("n")[8] == 2
    assert s.read_state("n")[9] == 1


def test_typed_tell_roundtrip_python_and_native():
    """Host tell with mtype must arrive with the exact type tag through both
    staging paths (bitcast through the stager's payload bytes)."""
    acct = make_account()
    for native in (False, True):
        s = BatchedSystem(capacity=8, behaviors=[acct], payload_width=4,
                          host_inbox=16, mailbox_slots=4,
                          native_staging=native)
        if native and s._stager is None:
            continue  # no compiler in env
        s.spawn_block(acct, 8)
        s.tell(3, np.asarray([50, 0, 0, 0], np.float32), mtype=SET)
        s.tell(3, np.asarray([20, 0, 0, 0], np.float32), mtype=WITHDRAW)
        s.tell(3, np.asarray([5, 0, 0, 0], np.float32), mtype=DEPOSIT)
        s.step()
        s.block_until_ready()
        assert s.read_state("balance")[3] == 35.0  # set 50, -20, +5 in order


@pytest.mark.slow
def test_bank_account_oracle_at_scale():
    """The VERDICT done-criterion shape: large actor count, multiple
    messages/actor/step, device == oracle bit-for-bit (at a size that keeps
    CI tractable)."""
    rng = np.random.default_rng(11)
    n = 1 << 16          # 65,536 accounts
    m = 1 << 18          # 262,144 messages (~4/actor)
    dst = rng.integers(0, n, m).astype(np.int32)
    mtype = rng.integers(0, 3, m).astype(np.int32)
    amount = rng.integers(1, 100, m).astype(np.float32)

    acct = make_account()
    s = BatchedSystem(capacity=n, behaviors=[acct], payload_width=4,
                      host_inbox=m, mailbox_slots=16, native_staging=False)
    s.spawn_block(acct, n)
    pl = np.zeros((m, 4), np.float32)
    pl[:, 0] = amount
    s.seed_inbox(dst, pl, mtype)
    s.step()
    s.block_until_ready()

    keep = np.zeros(m, bool)
    seen = np.zeros(n, np.int32)
    for i in np.argsort(dst, kind="stable"):
        d = int(dst[i])
        if seen[d] < 16:
            keep[i] = True
        seen[d] += 1
    bal_exp, rej_exp = bank_oracle(n, dst[keep], mtype[keep], amount[keep])
    np.testing.assert_array_equal(s.read_state("balance"), bal_exp)
    np.testing.assert_array_equal(s.read_state("rejected"), rej_exp)


def test_sharded_bank_account_cross_shard_fifo():
    """Slots mode on the 8-device mesh: typed ordered messages cross shards
    through the all_to_all and still apply in per-sender FIFO order."""
    from akka_tpu.batched.sharded import ShardedBatchedSystem

    n_accounts = 64  # 8 per shard on 8 devices
    acct = make_account(out_degree=2)

    @behavior("teller", {"target": ((), I32), "x": ((), F32)}, inbox="slots")
    def teller(state, mailbox: Mailbox, ctx):
        e = Emit.none(2, 4)
        e = Emit(
            dst=e.dst.at[0].set(state["target"]).at[1].set(state["target"]),
            payload=e.payload.at[0, 0].set(state["x"]).at[1, 0].set(1.0),
            valid=e.valid.at[0].set(True).at[1].set(True),
            type=e.type.at[0].set(SET).at[1].set(DEPOSIT),
        )
        return {}, e

    s = ShardedBatchedSystem(capacity=128, behaviors=[acct, teller],
                             payload_width=4, out_degree=2,
                             mailbox_slots=8, host_inbox_per_shard=64)
    s.spawn_block(acct, n_accounts)
    # tellers live on shards far from their targets: teller i (rows 64..127)
    # targets account (i*7) % 64 — guaranteed cross-shard traffic
    targets = ((np.arange(64) * 7) % n_accounts).astype(np.int32)
    xs = (100.0 + np.arange(64)).astype(np.float32)
    s.spawn_block(teller, 64, init_state={"target": targets, "x": xs})
    for t in range(64, 128):
        s.tell(t, np.zeros(4, np.float32))
    s.run(2)  # step 1: tellers emit; step 2: accounts apply
    s.block_until_ready()

    bal = s.read_state("balance")[:n_accounts]
    exp = np.zeros(n_accounts, np.float32)
    # delivery order on the receiving shard: exchange chunks are drained in
    # (source-shard, slot) order, and each source shard's slots are in its
    # stable emission order -> ascending teller id within a source shard,
    # source shards in ascending order. Teller ids ascend with shards here,
    # so global ascending teller id reproduces it.
    for sid in range(64):
        t = targets[sid]
        exp[t] = xs[sid]
        exp[t] += 1.0
    np.testing.assert_array_equal(bal, exp)
    assert s.mailbox_overflow == 0
    assert s.total_dropped == 0


def test_burst_4s_to_one_actor_arrives_completely_in_order():
    """VERDICT r2 #3 done-criterion: a burst of 4S messages to ONE slots
    actor arrives completely and in order via the spill region."""
    S = 4
    acct = make_account()
    s = BatchedSystem(capacity=4, behaviors=[acct], payload_width=4,
                      host_inbox=4 * S + 1, mailbox_slots=S,
                      native_staging=False)
    s.spawn_block(acct, 4)
    # 4S SET-then-DEPOSIT-style sequence whose final state encodes the order:
    # SET k at position k means the LAST set wins only if order holds
    m = 4 * S
    for k in range(m):
        s.tell(1, np.asarray([float(k), 0, 0, 0], np.float32), mtype=SET)
    s.tell(1, np.asarray([1.0, 0, 0, 0], np.float32), mtype=DEPOSIT)
    for _ in range(m // S + 2):
        s.step()
    s.block_until_ready()
    # all 17 messages applied, in order: last SET (m-1) then DEPOSIT 1
    assert s.read_state("balance")[1] == float(m - 1) + 1.0
    assert s.mailbox_overflow == 0
    assert s.dropped_messages == 0


def test_suspended_row_mail_retained_until_restart():
    """VERDICT r2 #3: mail addressed to a failed (suspended) row is HELD in
    the spill region — not dropped — and replays in order after the host
    restarts the row (FaultHandling queued-while-suspended parity)."""
    from akka_tpu.batched.step import fault_failed_rows

    @behavior("fragile", {"balance": ((), F32), "_failed": ((), jnp.bool_)},
              inbox="slots")
    def fragile(state, mailbox: Mailbox, ctx):
        def apply(carry, t, pl):
            bal, failed = carry
            return (jnp.where(t == SET, pl[0],
                              jnp.where(t == DEPOSIT, bal + pl[0], bal)),
                    failed | (t == 99))  # type 99 = poison -> fail

        bal, failed = mailbox.fold((state["balance"], state["_failed"]), apply)
        return {"balance": bal, "_failed": failed}, Emit.none(1, 4)

    s = BatchedSystem(capacity=4, behaviors=[fragile], payload_width=4,
                      host_inbox=16, mailbox_slots=4, native_staging=False)
    s.spawn_block(fragile, 4)
    # poison row 2 -> it fails during this step (state discarded, flag set)
    s.tell(2, np.zeros(4, np.float32), mtype=99)
    s.step()
    s.block_until_ready()
    assert list(fault_failed_rows(s.state)) == [2]

    # mail sent WHILE suspended: held, not dropped
    s.tell(2, np.asarray([40.0, 0, 0, 0], np.float32), mtype=SET)
    s.tell(2, np.asarray([2.0, 0, 0, 0], np.float32), mtype=DEPOSIT)
    s.step()
    s.step()
    s.block_until_ready()
    assert s.read_state("balance")[2] == 0.0   # still suspended, nothing ran
    assert s.mailbox_overflow == 0             # ... and nothing was lost

    # restart (keeps zeroed state, clears the flag); held mail replays in order
    s.restart_rows([2])
    s.step()
    s.block_until_ready()
    assert s.read_state("balance")[2] == 42.0  # SET 40 then DEPOSIT 2
    assert s.mailbox_overflow == 0


def test_burst_and_suspension_on_8_device_mesh():
    """VERDICT r2 #3 done-criterion: both spill behaviors hold on the
    sharded runtime (spill region ahead of the all_to_all exchange)."""
    from akka_tpu.batched.sharded import ShardedBatchedSystem
    from akka_tpu.batched.step import fault_failed_rows

    S = 4

    @behavior("sfragile", {"balance": ((), F32), "_failed": ((), jnp.bool_)},
              inbox="slots")
    def sfragile(state, mailbox: Mailbox, ctx):
        def apply(carry, t, pl):
            bal, failed = carry
            return (jnp.where(t == SET, pl[0],
                              jnp.where(t == DEPOSIT, bal + pl[0], bal)),
                    failed | (t == 99))

        bal, failed = mailbox.fold((state["balance"], state["_failed"]), apply)
        return {"balance": bal, "_failed": failed}, Emit.none(1, 4)

    s = ShardedBatchedSystem(capacity=16, behaviors=[sfragile],
                             payload_width=4, mailbox_slots=S,
                             host_inbox_per_shard=4 * S + 1)
    s.spawn_block(sfragile, 16)
    # burst of 4S ordered SETs + a DEPOSIT to one actor (row 9, shard 4 on 8
    # devices) — must fully arrive through the per-shard spill region
    m = 4 * S
    for k in range(m):
        s.tell(9, np.asarray([float(k), 0, 0, 0], np.float32), mtype=SET)
    s.tell(9, np.asarray([1.0, 0, 0, 0], np.float32), mtype=DEPOSIT)
    s.run(m // S + 2)
    s.block_until_ready()
    assert s.read_state("balance")[9] == float(m - 1) + 1.0
    assert s.mailbox_overflow == 0

    # suspension on the mesh: poison row 3, send while suspended, restart
    s.tell(3, np.zeros(4, np.float32), mtype=99)
    s.run(1)
    s.block_until_ready()
    assert 3 in list(fault_failed_rows(s.state))
    s.tell(3, np.asarray([40.0, 0, 0, 0], np.float32), mtype=SET)
    s.tell(3, np.asarray([2.0, 0, 0, 0], np.float32), mtype=DEPOSIT)
    s.run(2)
    s.block_until_ready()
    assert s.read_state("balance")[3] == 0.0
    s.restart_rows([3])
    s.run(1)
    s.block_until_ready()
    assert s.read_state("balance")[3] == 42.0
    assert s.mailbox_overflow == 0


def test_reduce_exact_past_slot_cap():
    """A reduce-kind behavior in a slots-mode system must see ALL messages
    in its sum/count even when they exceed the slot capacity (the slot cap
    bounds ordered processing, not commutative aggregation)."""
    acct = make_account()

    @behavior("counter", {"total": ((), F32), "n": ((), I32)})
    def counter(state, inbox, ctx):
        return ({"total": state["total"] + inbox.sum[0],
                 "n": state["n"] + inbox.count}, Emit.none(1, 4))

    m = 64  # all to one counter actor, slots = 4 << 64
    s = BatchedSystem(capacity=4, behaviors=[acct, counter], payload_width=4,
                      host_inbox=m, mailbox_slots=4, native_staging=False)
    s.spawn_block(acct, 2)
    s.spawn_block(counter, 2)
    pl = np.zeros((m, 4), np.float32)
    pl[:, 0] = np.arange(1, m + 1)
    s.seed_inbox(np.full(m, 2, np.int32), pl, np.zeros(m, np.int32))
    s.step()
    s.block_until_ready()
    assert s.read_state("total")[2] == float(m * (m + 1) // 2)  # exact
    assert s.read_state("n")[2] == m
    # nothing was lost: the recipient is reduce-kind, so slot-cap overflow is
    # NOT a drop (the exact aggregation applied every message) and must not
    # be reported as phantom loss
    assert s.mailbox_overflow == 0


def test_counting_slots_vs_wide_budget(monkeypatch):
    """ISSUE 6 tentpole budget: the counting-sort slots path must stay
    >= 5x faster than the wide-sort kernel's ms/step at 64k actors on a
    CPU (measured ~7x live, ~12x on a quiet box: 28ms vs 196ms). Both
    legs are timed best-of interleaved under the same load so machine
    noise cancels in the ratio; a rank phase regressing toward a payload
    sort collapses it to ~1x regardless of the constant."""
    monkeypatch.setattr(sg, "_auto_rank_strategy",
                        lambda m, n, platform: "counting")
    m, n = (1 << 16) + 8, 1 << 16
    rng = np.random.default_rng(7)
    dst = jnp.asarray(rng.integers(0, n, size=m).astype(np.int32))
    mtype = jnp.ones((m,), jnp.int32)
    payload = jnp.asarray(rng.standard_normal((m, 4)).astype(np.float32))
    ok = jnp.ones((m,), bool)

    def make(backend):
        def slots(d, t, p, v):
            out, place, _ = sg.deliver_slots(d, t, p, v, n, 2,
                                             backend=backend)
            return out, place(n)
        return jax.jit(slots)

    fc, fw = make("xla"), make("reference")
    jax.block_until_ready(fc(dst, mtype, payload, ok))   # compile
    jax.block_until_ready(fw(dst, mtype, payload, ok))
    bc = bw = float("inf")
    for attempt in range(2):
        for _ in range(4):
            t0 = time.perf_counter()
            jax.block_until_ready(fc(dst, mtype, payload, ok))
            bc = min(bc, time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(fw(dst, mtype, payload, ok))
            bw = min(bw, time.perf_counter() - t0)
        if bw >= 5.0 * bc:
            break
        # conditional second best-of window: a cross-suite load spike
        # inflates the fast leg's min; a rank-phase regression stays ~1x
    assert bw >= 5.0 * bc, (
        f"counting slots {bc * 1e3:.1f}ms/step vs wide reference "
        f"{bw * 1e3:.1f}ms/step at 64k: ratio {bw / bc:.1f} fell under "
        f"the 5x budget — the counting rank phase has regressed")
