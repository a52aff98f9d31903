"""Preemption-tolerant batched runtime (ISSUE 4): checkpoint barrier,
write-ahead tell journal, crash-recovery rebuild.

The kill/restore/continue tests simulate preemption the only way an
in-process suite honestly can: run a victim system, ABANDON it at a
murmur3-chosen point (no drain, no goodbye — whatever the snapshot and the
fsync'd journal hold on disk is all recovery gets), rebuild a fresh system
from disk, continue it to the horizon, and require BIT-PARITY with an
uninterrupted twin and a numpy oracle. Every assertion is exact: snapshots
are complete slab dumps, the journal replays staged batches at their
recorded step counters, and the chaos schedule is a pure function of
(seed, step, lane).
"""

import glob
import os
import pickle
import struct
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from akka_tpu.actor.supervision import Directive
from akka_tpu.batched import BatchedSystem, Emit, LaneSupervisor, behavior
from akka_tpu.batched.bridge import BatchedRuntimeHandle, RecoveredAskLost
from akka_tpu.batched.sharded import ShardedBatchedSystem
from akka_tpu.event.flight_recorder import InMemoryFlightRecorder
from akka_tpu.persistence.journal import repair_record_log, scan_record_log
from akka_tpu.persistence.slab_snapshot import (SCHEMA_VERSION,
                                                latest_slab_path,
                                                slab_pytree)
from akka_tpu.persistence.tell_journal import TellJournal
from akka_tpu.testkit import chaos

P = 4


def make_sum(name="sum"):
    """Pure fan-in accumulator: state is exactly the sum of delivered
    payload column 0 — the oracle is the tell schedule itself."""

    @behavior(name, {"total": ((), jnp.float32)})
    def summer(state, inbox, ctx):
        return {"total": state["total"] + inbox.sum[0]}, Emit.none(1, P)

    return summer


def make_acc(supervisor=None, name="acc"):
    @behavior(name, {"acc": ((), jnp.float32)}, always_on=True,
              supervisor=supervisor)
    def acc(state, inbox, ctx):
        return {"acc": state["acc"] + 1.0}, Emit.none(1, P)

    return acc


def make_ring():
    @behavior("ring", {"received": ((), jnp.int32), "last": ((), jnp.float32)})
    def ring(state, inbox, ctx):
        nxt = (ctx.actor_id + 1) % ctx.n_actors
        token = inbox.sum[0]
        return ({"received": state["received"] + inbox.count,
                 "last": token.astype(jnp.float32)},
                Emit.single(nxt, jnp.stack([token + 1, 0.0, 0.0, 0.0]), 1, P,
                            when=inbox.count > 0))
    return ring


def tell_schedule(seed, n, steps, every=3):
    """Deterministic tell plan: {step: (dst_rows, value)}."""
    sched = {}
    for s in range(steps):
        if s % every == 0:
            dst = np.asarray([int(chaos.chaos_hash(seed, s, 0) % n)])
            sched[s] = (dst, float(1 + s % 5))
    return sched


def drive(sys_, sched, upto, journal=None, staged=()):
    """Step `sys_` to host step `upto`, staging scheduled tells at their
    step counters; `staged` = schedule steps already staged pre-kill
    (replayed by the journal — re-telling would double-deliver)."""
    while sys_._host_step < upto:
        s = sys_._host_step
        if s in sched and s not in staged:
            dst, val = sched[s]
            pl = np.zeros((len(dst), P), np.float32)
            pl[:, 0] = val
            sys_.tell(dst, pl)
        sys_.step()


def sum_oracle(sched, n, upto):
    """A tell staged at host step c is delivered by dispatch c+1: totals at
    step `upto` include exactly the schedule entries with c <= upto-1."""
    out = np.zeros(n, np.float32)
    for s, (dst, val) in sched.items():
        if s <= upto - 1:
            out[dst] += val
    return out


# ------------------------------------------------------------ schema v2
def test_v2_snapshot_roundtrip_all_slabs(tmp_path):
    seed, rate, n, steps = 11, 0.08, 32, 25
    sup = LaneSupervisor(directive=Directive.RESTART)
    b = chaos.inject(make_acc(sup), seed=seed, crash_rate=rate)
    a = BatchedSystem(n, [b], payload_width=P)
    a.spawn_block(0, n)
    for _ in range(steps):
        a.step()
    assert a.supervision_counts["failed"] > 0  # v2 payload is non-trivial
    path = a.checkpoint(str(tmp_path))

    tree = slab_pytree(a)
    assert int(tree["schema_version"]) == SCHEMA_VERSION

    c = BatchedSystem(n, [b], payload_width=P)
    c.spawn_block(0, n)
    c.restore(path)
    for col in a.state:
        np.testing.assert_array_equal(
            np.asarray(a.state[col]), np.asarray(c.state[col]), err_msg=col)
    for k in ("behavior_id", "alive", "step_count", "mail_dropped",
              "sup_counts", "attention", "inbox_dst", "inbox_type",
              "inbox_payload", "inbox_valid"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, k)), np.asarray(getattr(c, k)), err_msg=k)
    assert c._host_step == a._host_step

    # determinism past the snapshot: chaos is a pure function of the
    # restored step counter, so both must stay bit-identical
    for _ in range(10):
        a.step()
        c.step()
    np.testing.assert_array_equal(np.asarray(a.read_state("acc")),
                                  np.asarray(c.read_state("acc")))
    assert a.supervision_counts == c.supervision_counts


def test_v1_snapshot_upgrade_zero_fills(tmp_path):
    """A v1 snapshot (core slabs only, no schema_version) restored into a
    supervised runtime must reset every post-v1 slab to its reserved
    fill — not inherit the target's dirty pre-restore values."""
    n = 16
    sup = LaneSupervisor(directive=Directive.RESTART, min_backoff_steps=2,
                         max_backoff_steps=8)
    b = chaos.inject(make_acc(sup), seed=5, crash_rate=0.1)
    src = BatchedSystem(n, [b], payload_width=P)
    src.spawn_block(0, n)
    for _ in range(12):
        src.step()
    tree = slab_pytree(src)

    # strip the snapshot down to what a v1 writer produced
    flat = {}
    for col, arr in tree["state"].items():
        if not col.startswith("_"):  # v1 predates the supervision columns
            flat[f"state.{col}"] = arr
    for k in ("behavior_id", "alive", "step_count", "inbox_dst",
              "inbox_type", "inbox_payload", "inbox_valid"):
        flat[k] = tree[k]
    v1 = str(tmp_path / "slab-12.npz")
    np.savez(v1, **flat)

    dst = BatchedSystem(n, [b], payload_width=P)
    dst.spawn_block(0, n)
    for _ in range(20):  # dirty the target's counters/backoff state
        dst.step()
    dst.restore(v1)
    np.testing.assert_array_equal(np.asarray(dst.read_state("acc")),
                                  np.asarray(src.read_state("acc")))
    assert int(np.asarray(dst.step_count)) == 12
    # v2 aggregates and supervision columns: reserved fills, not stale
    assert int(np.asarray(dst.sup_counts).sum()) == 0
    np.testing.assert_array_equal(np.asarray(dst.read_state("_retries")),
                                  np.zeros(n))
    np.testing.assert_array_equal(np.asarray(dst.read_state("_restart_at")),
                                  np.full(n, -1))  # re-armed, not pending


def test_newer_schema_refused(tmp_path):
    n = 8
    b = make_sum()
    a = BatchedSystem(n, [b], payload_width=P)
    a.spawn_block(0, n)
    path = a.checkpoint(str(tmp_path))
    from akka_tpu.persistence.slab_snapshot import (load_slab_tree,
                                                    restore_slab_pytree)
    tree = dict(load_slab_tree(path))
    tree["schema_version"] = np.int64(SCHEMA_VERSION + 1)
    with pytest.raises(ValueError, match="newer"):
        restore_slab_pytree(a, tree)


# ------------------------------------------------------------ journal log
def test_torn_tail_truncated_not_fatal(tmp_path):
    path = str(tmp_path / "tells.wal")
    j = TellJournal(path)
    for s in range(3):
        j.append(s, "tell", np.asarray([s]), np.ones((1, P), np.float32),
                 np.asarray([0]))
    j.close()
    good_size = os.path.getsize(path)
    # torn tail: a record whose length prefix promises more than the crash
    # let the filesystem keep (the pre-fix behavior: UnpicklingError at
    # every subsequent open)
    blob = pickle.dumps({"step": 3, "kind": "tell"}, protocol=4)
    with open(path, "ab") as f:
        f.write(struct.pack("<Q", len(blob)) + blob[: len(blob) // 2])

    fr = InMemoryFlightRecorder()
    j2 = TellJournal(path, flight_recorder=fr)
    assert j2.truncated_bytes > 0
    recs = list(j2.records())
    assert [r["step"] for r in recs] == [0, 1, 2]  # intact prefix survives
    assert os.path.getsize(path) == good_size  # tail physically gone
    evs = fr.of_type("journal_truncated")
    assert evs and evs[0]["dropped_bytes"] == j2.truncated_bytes
    # append after repair: clean continuation, no gap
    j2.append(3, "tell", np.asarray([0]), np.ones((1, P), np.float32),
              np.asarray([0]))
    assert [r["step"] for r in j2.records()] == [0, 1, 2, 3]
    j2.close()


def test_repair_record_log_garbage_tail(tmp_path):
    path = str(tmp_path / "events.log")
    with open(path, "wb") as f:
        for i in range(4):
            blob = pickle.dumps({"i": i}, protocol=4)
            f.write(struct.pack("<Q", len(blob)) + blob)
        f.write(b"\x07garbage")  # short header
    dropped = repair_record_log(path)
    assert dropped == len(b"\x07garbage")
    assert [obj["i"] for _end, obj in scan_record_log(path)] == [0, 1, 2, 3]
    assert repair_record_log(path) == 0  # idempotent on a clean log


def test_journal_compacts_at_checkpoint(tmp_path):
    n = 8
    b = make_sum()
    sys_ = BatchedSystem(n, [b], payload_width=P)
    sys_.spawn_block(0, n)
    sys_.tell_journal = TellJournal(str(tmp_path / "tells.wal"))
    pl = np.ones((1, P), np.float32)
    for s in range(6):
        sys_.tell(np.asarray([0]), pl)
        sys_.step()
    assert len(list(sys_.tell_journal.records())) == 6
    sys_.checkpoint(str(tmp_path))
    # every journaled batch is in the snapshot -> compacted away
    assert all(r["step"] >= sys_._host_step
               for r in sys_.tell_journal.records())


# ------------------------------------------- kill / restore / continue
@pytest.mark.parametrize("backend", [None, "reference"])
@pytest.mark.parametrize("phase", ["staging", "pipeline-full"])
def test_kill_restore_continue_parity(tmp_path, backend, phase):
    seed, n, horizon = 23, 32, 30
    sched = tell_schedule(seed, n, horizon)
    b = make_sum()

    # uninterrupted twin -> truth, cross-checked against the numpy oracle
    ref = BatchedSystem(n, [b], payload_width=P, delivery_backend=backend)
    ref.spawn_block(0, n)
    drive(ref, sched, horizon)
    truth = np.asarray(ref.read_state("total"))
    np.testing.assert_array_equal(truth, sum_oracle(sched, n, horizon))

    # victim: checkpoint mid-run, then die at a murmur3-chosen point
    ckpt_at = 8 + int(chaos.chaos_hash(seed, 1, 0) % 6)       # 8..13
    kill_at = ckpt_at + 2 + int(chaos.chaos_hash(seed, 2, 0) % 6)
    victim = BatchedSystem(n, [b], payload_width=P, delivery_backend=backend)
    victim.spawn_block(0, n)
    victim.tell_journal = TellJournal(str(tmp_path / "tells.wal"))
    drive(victim, sched, ckpt_at)
    victim.checkpoint(str(tmp_path))
    drive(victim, sched, kill_at)
    staged_pre_kill = {s for s in sched if s < kill_at}
    if phase == "staging":
        # die with a batch journaled + staged but NOT yet dispatched
        s = kill_at
        if s in sched:
            dst, val = sched[s]
            pl = np.zeros((len(dst), P), np.float32)
            pl[:, 0] = val
            victim.tell(dst, pl)
            staged_pre_kill.add(s)
    else:
        # die inside an undrained pipelined window: dispatches in flight,
        # no block_until_ready, no goodbye
        victim.run_pipelined(3, depth=2)
    del victim  # the crash: disk state is all recovery gets

    fresh = BatchedSystem(n, [b], payload_width=P, delivery_backend=backend)
    fresh.spawn_block(0, n)
    j = TellJournal(str(tmp_path / "tells.wal"))
    fresh.restore(latest_slab_path(str(tmp_path)), journal=j)
    assert fresh._host_step >= ckpt_at
    drive(fresh, sched, horizon, staged=staged_pre_kill)
    np.testing.assert_array_equal(np.asarray(fresh.read_state("total")),
                                  truth)


@pytest.mark.parametrize("backend", [None, "reference"])
def test_kill_in_backoff_window_parity(tmp_path, backend):
    """Phase 3: die while restarts are parked in an exponential-backoff
    window (_restart_at > step). The pending-deadline columns live in the
    snapshot, so the restored run must fire exactly the same restarts at
    exactly the same steps as the uninterrupted twin."""
    seed, rate, n, horizon = 17, 0.08, 32, 40
    sup = LaneSupervisor(directive=Directive.RESTART, min_backoff_steps=2,
                         max_backoff_steps=8)
    b = chaos.inject(make_acc(sup), seed=seed, crash_rate=rate)

    # probe: find the steps where some lane sits in a backoff window
    probe = BatchedSystem(n, [b], payload_width=P, delivery_backend=backend)
    probe.spawn_block(0, n)
    active = []
    for s in range(1, horizon):
        probe.step()
        if np.any(np.asarray(probe.read_state("_restart_at")) > s):
            active.append(s)
    assert active, "chaos config produced no backoff windows to kill in"
    kill_at = active[int(chaos.chaos_hash(seed, 3, 0) % len(active))]
    for _ in range(horizon - probe._host_step):
        probe.step()
    truth = {
        "acc": np.asarray(probe.read_state("acc")),
        "_retries": np.asarray(probe.read_state("_retries")),
        "_restart_at": np.asarray(probe.read_state("_restart_at")),
        "_gen": np.asarray(probe.read_state("_gen")),
        "_failed": np.asarray(probe.read_state("_failed")),
        "counts": probe.supervision_counts,
    }

    victim = BatchedSystem(n, [b], payload_width=P, delivery_backend=backend)
    victim.spawn_block(0, n)
    for _ in range(kill_at):
        victim.step()
    victim.checkpoint(str(tmp_path))  # barrier INSIDE the backoff window
    victim.run_pipelined(2, depth=2)  # undrained work past the snapshot
    del victim

    fresh = BatchedSystem(n, [b], payload_width=P, delivery_backend=backend)
    fresh.spawn_block(0, n)
    fresh.restore(latest_slab_path(str(tmp_path)))
    assert np.any(np.asarray(fresh.read_state("_restart_at"))
                  > fresh._host_step)  # restored mid-window, deadline armed
    for _ in range(horizon - fresh._host_step):
        fresh.step()
    for key in ("acc", "_retries", "_restart_at", "_gen", "_failed"):
        np.testing.assert_array_equal(np.asarray(fresh.read_state(key)),
                                      truth[key], err_msg=key)
    assert fresh.supervision_counts == truth["counts"]


# ----------------------------------------------------- sharded re-shard
def test_sharded_restore_across_device_counts(tmp_path):
    """Snapshot on an 8-shard mesh, restore on 4 shards: the global row
    space is mesh-agnostic, so in-flight ring tokens must keep moving and
    land bit-identically to the 8-shard continuation."""
    assert jax.device_count() >= 8, "conftest must force 8 CPU devices"
    n = 32
    ring = make_ring()
    a = ShardedBatchedSystem(capacity=n, behaviors=[ring], n_devices=8,
                             payload_width=P)
    a.spawn_block(ring, n)
    a.tell(0, [1.0, 0, 0, 0])
    for _ in range(10):
        a.run(1)
    a.checkpoint(str(tmp_path))
    for _ in range(15):
        a.run(1)
    truth_recv = np.asarray(a.read_state("received"))
    truth_last = np.asarray(a.read_state("last"))
    truth_counts = {k: int(v) for k, v in a.supervision_counts.items()} \
        if hasattr(a, "supervision_counts") else None

    b = ShardedBatchedSystem(capacity=n, behaviors=[ring], n_devices=4,
                             payload_width=P)
    b.spawn_block(ring, n)
    step = b.restore(latest_slab_path(str(tmp_path)))
    assert step == 10 and b.n_shards == 4
    b.run_pipelined(15, depth=2)  # post-restore pipelined stepping works
    np.testing.assert_array_equal(np.asarray(b.read_state("received")),
                                  truth_recv)
    np.testing.assert_array_equal(np.asarray(b.read_state("last")),
                                  truth_last)
    if truth_counts is not None:
        assert {k: int(v) for k, v in b.supervision_counts.items()} \
            == truth_counts


def test_sharded_restore_same_count_direct(tmp_path):
    n = 32
    ring = make_ring()
    a = ShardedBatchedSystem(capacity=n, behaviors=[ring], n_devices=4,
                             payload_width=P)
    a.spawn_block(ring, n)
    a.tell(0, [1.0, 0, 0, 0])
    for _ in range(7):
        a.run(1)
    a.checkpoint(str(tmp_path))
    b = ShardedBatchedSystem(capacity=n, behaviors=[ring], n_devices=4,
                             payload_width=P)
    b.spawn_block(ring, n)
    b.restore(latest_slab_path(str(tmp_path)))
    for s in (a, b):
        for _ in range(5):
            s.run(1)
    np.testing.assert_array_equal(np.asarray(a.read_state("received")),
                                  np.asarray(b.read_state("received")))


# ------------------------------------------------------ bridge recovery
def _bridge(tmp_path, fr=None, interval=0, keep=3):
    return BatchedRuntimeHandle(capacity=64, payload_width=8,
                                promise_rows=8, flight_recorder=fr,
                                checkpoint_interval_steps=interval,
                                checkpoint_dir=str(tmp_path),
                                checkpoint_keep=keep)


def make_bridge_sum():
    @behavior("bsum", {"total": ((), jnp.float32)})
    def bsum(state, inbox, ctx):
        return {"total": state["total"] + inbox.sum[0]}, Emit.none(1, 8)
    return bsum


def test_outstanding_ask_fails_recovered_not_hangs(tmp_path):
    b = make_bridge_sum()  # blackhole: never emits a reply
    h = _bridge(tmp_path)
    rows = h.spawn(b, 4)
    for i in range(6):
        h.tell(int(rows[0]), float(i))
        h.step()
    h.checkpoint()
    fut = h.ask(int(rows[0]), 1.0, timeout=30.0)  # would hang 30s pre-fix
    t0 = time.monotonic()
    h.restore()
    exc = fut.exception(timeout=2.0)
    assert isinstance(exc, RecoveredAskLost)
    assert "promise row" in str(exc)
    assert time.monotonic() - t0 < 5.0  # failed fast, not at ask timeout
    # the slot returned to the free list with its latch lowered: a fresh
    # ask on the recovered runtime must still work end-to-end
    assert len(h._promise_free) == h.promise_rows_n
    h.tell(int(rows[0]), 100.0)
    h.step()
    assert float(h.read_state("total", rows[:1])[0]) >= 100.0
    h.shutdown()


def test_bridge_restore_continue_parity(tmp_path):
    b = make_bridge_sum()
    h = _bridge(tmp_path, interval=4, keep=2)
    rows = h.spawn(b, 8)
    for i in range(12):
        h.tell(int(rows[0]), float(i))
        h.step()
    truth = float(h.read_state("total", rows[:1])[0])
    assert truth == float(sum(range(12)))
    step = h.restore()  # snapshot + journal replay reconstruct the frontier
    assert step > 0
    h.tell(int(rows[0]), 100.0)
    h.step()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:  # pump may still be draining replay
        got = float(h.read_state("total", rows[:1])[0])
        if got == truth + 100.0:
            break
        time.sleep(0.02)
    assert got == truth + 100.0
    h.shutdown()


@pytest.mark.parametrize("interval, steps, at_least", [(8, 40, 2),
                                                       (256, 300, 1)])
def test_auto_cadence_takes_and_gcs_snapshots(tmp_path, interval, steps,
                                              at_least):
    fr = InMemoryFlightRecorder()
    b = make_bridge_sum()
    h = _bridge(tmp_path, fr=fr, interval=interval, keep=2)
    rows = h.spawn(b, 4)
    for _ in range(steps):
        h.tell(int(rows[0]), 1.0)
        h.step()
    # the cadence's barrier snapshots cost the run no tell: counts exact
    assert float(h.read_state("total", rows[:1])[0]) == float(steps)
    st = h.checkpoint_stats()
    assert st["checkpoints"] >= at_least
    assert st["last_size_bytes"] > 0 and st["last_duration_s"] > 0
    assert st["last_path"] and os.path.exists(st["last_path"])
    evs = fr.of_type("device_checkpoint")
    assert len(evs) == st["checkpoints"]
    assert all(e["size_bytes"] > 0 for e in evs)
    # retained-snapshot GC: at most `keep` finished snapshots on disk
    snaps = [p for p in glob.glob(os.path.join(str(tmp_path), "slab-*"))
             if "tmp" not in os.path.basename(p)]
    assert 1 <= len(snaps) <= 2, snaps
    h.shutdown()


def test_checkpoint_io_failure_degrades_to_running(tmp_path):
    """ISSUE 4 tentpole #4: a sick checkpoint target must cost a flight-
    recorder warning, never a stalled or crashed step loop."""
    bad = str(tmp_path / "not-a-dir")
    with open(bad, "w") as f:
        f.write("file where a directory should be")
    fr = InMemoryFlightRecorder()
    b = make_bridge_sum()
    h = BatchedRuntimeHandle(capacity=64, payload_width=8, promise_rows=8,
                             flight_recorder=fr,
                             checkpoint_interval_steps=4,
                             checkpoint_dir=bad, checkpoint_keep=2)
    rows = h.spawn(b, 4)
    for _ in range(40):
        h.tell(int(rows[0]), 1.0)
        h.step()
    assert float(h.read_state("total", rows[:1])[0]) == 40.0
    assert fr.of_type("checkpoint_failed")  # warned, did not raise
    assert h.checkpoint_stats()["checkpoints"] == 0
    h.shutdown()


# -------------------------------------------- implicit drain on reads
def test_read_state_drains_pipeline_first():
    """read_state/failed_rows during an undrained pipelined window must
    see the settled slabs (donated buffers can report ready early), so
    both drain to quiescence before the host read."""
    n = 16
    b = make_acc()
    sys_ = BatchedSystem(n, [b], payload_width=P)
    sys_.spawn_block(0, n)
    for _ in range(3):  # dispatch without any sync in between
        sys_.step()
    acc = np.asarray(sys_.read_state("acc"))  # no explicit block: implicit
    np.testing.assert_array_equal(acc, np.full(n, 3.0))
    assert sys_.failed_rows().size == 0


def test_config_wires_checkpoint_keys(tmp_path):
    from akka_tpu.config import Config
    from akka_tpu.dispatch.batched import TpuBatchedDispatcher

    class _Disp:
        pass

    cfg = Config({"capacity": 64, "payload-width": 8, "promise-rows": 8,
                  "checkpoint-interval-steps": 16,
                  "checkpoint-dir": str(tmp_path), "checkpoint-keep": 5})
    d = TpuBatchedDispatcher(_Disp(), "tpu-dispatcher", cfg)
    h = d.handle()
    assert h.checkpoint_interval_steps == 16
    assert h.checkpoint_dir == str(tmp_path)
    assert h.checkpoint_keep == 5
    d2 = TpuBatchedDispatcher(_Disp(), "tpu-dispatcher",
                              Config({"capacity": 64}))
    h2 = d2.handle()
    assert h2.checkpoint_interval_steps == 0  # default: disarmed
    assert h2.checkpoint_dir is None
    h.shutdown()
    h2.shutdown()
