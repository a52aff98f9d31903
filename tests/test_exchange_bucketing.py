"""The two sides of the exchange's bucketing seam fill the same send buffers
(batched/sharded.py): the ranked side scatters from the original domain, the
sorted side copies each destination shard's contiguous run out of the one
keyed sort. Whole systems built on `delivery_backend="reference"` (sorted)
and `"xla"` (ranked) must agree bit for bit on everything the exchange feeds
(the next inbox, the drop counters, every state column), and the sorted
side alone must agree with a numpy model of what a send buffer is."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_tpu.batched import Emit, Mailbox, behavior
from akka_tpu.batched.sharded import ShardedBatchedSystem, _bucket_by_sort

I32, F32 = jnp.int32, jnp.float32
K, P_W, LOCAL_N = 2, 4, 16
STEPS = (1, 3, 8)
DEVICES = (1, 2, 4, 8)


def _emit(state, ctx, count):
    """K tells a step from every actor: destinations walk by `stride` a
    step over [-2, n_actors + 1] (so some are -1 or below and some >=
    n_global), one in `gap` is withheld, the payload names sender, step,
    lane and what the sender's own inbox held."""
    lane = jnp.arange(K, dtype=I32)
    span = ctx.n_actors + 4
    dst = (state["to"] + 2 + state["stride"] * ctx.step * (lane + 1)) \
        % span - 2
    gap = state["gap"]
    valid = (gap == 0) | ((ctx.actor_id + ctx.step + lane)
                          % jnp.maximum(gap, 1) != 0)
    payload = jnp.stack([
        jnp.broadcast_to(ctx.actor_id, (K,)),
        jnp.broadcast_to(ctx.step % 16, (K,)), lane,
        jnp.broadcast_to(count, (K,))], axis=1).astype(F32)
    mtype = 1 + (ctx.actor_id + lane + ctx.step) % 3
    return Emit(dst=dst, payload=payload, valid=valid, type=mtype)


WIRING = {"to": ((K,), I32), "stride": ((), I32), "gap": ((), I32)}


@behavior("spray", {**WIRING, "received": ((), I32), "acc": ((), F32)},
          always_on=True)
def spray(state, inbox, ctx):
    return ({**state, "received": state["received"] + inbox.count,
             "acc": state["acc"] + inbox.sum[0] + 3 * inbox.sum[1]
             + 5 * inbox.sum[3]},
            _emit(state, ctx, inbox.count))


@behavior("spray_slots", {**WIRING, "received": ((), I32), "h": ((), I32)},
          inbox="slots", always_on=True)
def spray_slots(state, mailbox: Mailbox, ctx):
    # order-sensitive: two mailboxes with the same messages in another
    # order fold to another h
    h = mailbox.fold(state["h"], lambda h, t, pl: (
        h * 31 + t * 7 + pl[0].astype(I32) + 3 * pl[2].astype(I32))
        & 0xFFFF)
    return ({**state, "received": state["received"] + mailbox.count,
             "h": h}, _emit(state, ctx, mailbox.count))


def _wiring(case: str, n: int):
    rng = np.random.default_rng(7)
    if case == "to_one_shard":
        # the last shard receives everything, every other shard nothing
        to = rng.integers(n - LOCAL_N, n, size=(n, K))
        return to, np.zeros(n), np.zeros(n)
    if case == "cap_exact":
        # lane 0 to shard 0, lane 1 to the sender's own shard, nothing
        # withheld: every run is LOCAL_N rows = pair_cap exactly, except
        # shard 0's own (2 * LOCAL_N: the second half is dropped)
        own = (np.arange(n) // LOCAL_N) * LOCAL_N
        to = np.stack([rng.integers(0, LOCAL_N, size=n),
                       own + rng.integers(0, LOCAL_N, size=n)], axis=1)
        return to, np.zeros(n), np.zeros(n)
    to = rng.integers(-2, n + 2, size=(n, K))
    return to, rng.choice([0, 1, 3], size=n), np.full(n, 5)


CASES = {
    # case -> (behavior, constructor arguments)
    "random": (spray, {}),
    "to_one_shard": (spray, {}),
    "cap2": (spray, {"remote_capacity_per_pair": 2}),
    "cap_exact": (spray, {"remote_capacity_per_pair": LOCAL_N}),
    "slots": (spray_slots, {"mailbox_slots": 4}),
    "stray": (spray, {"reroute_strays": True}),
}


def _build(case: str, n_devices: int, backend: str) -> ShardedBatchedSystem:
    b, kwargs = CASES[case]
    n = LOCAL_N * n_devices
    s = ShardedBatchedSystem(capacity=n, behaviors=[b], n_devices=n_devices,
                             payload_width=P_W, out_degree=K,
                             host_inbox_per_shard=8,
                             delivery_backend=backend, **kwargs)
    to, stride, gap = _wiring(case, n)
    s.spawn_block(b, n, init_state={"to": to.astype(np.int32),
                                    "stride": stride.astype(np.int32),
                                    "gap": gap.astype(np.int32)})
    return s


def _snapshot(s: ShardedBatchedSystem) -> dict:
    snap = {"inbox_dst": s.inbox_dst, "inbox_payload": s.inbox_payload,
            "inbox_valid": s.inbox_valid, "inbox_type": s.inbox_type,
            "dropped": s.dropped, "mail_dropped": s.mail_dropped,
            **{f"state.{k}": v for k, v in s.state.items()}}
    return {k: np.asarray(jax.device_get(v)) for k, v in snap.items()}


def _misroute(s: ShardedBatchedSystem) -> None:
    """What a rebalance leaves behind: a third of the inbox's messages now
    name a row of the NEXT shard, so they sit in the wrong shard's inbox."""
    moved = (s.inbox_dst >= 0) & (s.inbox_dst % 3 == 0)
    s.inbox_dst = jnp.where(moved, (s.inbox_dst + LOCAL_N) % s.capacity,
                            s.inbox_dst)


@functools.lru_cache(maxsize=None)
def _run(case: str, n_devices: int, backend: str) -> dict:
    """Snapshots after 1, 3 and 8 steps (in the stray case: stray-mode
    steps, after two ordinary ones and a rebalance's misrouting)."""
    s = _build(case, n_devices, backend)
    if case == "stray":
        s.run(1)
        s.run(1)
        s.enter_stray_mode()
        _misroute(s)
    snaps, done = {}, 0
    for t in STEPS:
        for _ in range(t - done):
            s.run(1)    # one step program whatever the count
        done = t
        snaps[t] = _snapshot(s)
        if case == "stray":
            _misroute(s)
    return snaps


@pytest.mark.parametrize("steps", STEPS)
@pytest.mark.parametrize("n_devices", DEVICES)
@pytest.mark.parametrize("case", list(CASES))
def test_sorted_and_ranked_sides_leave_the_same_system(case, n_devices, steps):
    ref = _run(case, n_devices, "reference")[steps]
    xla = _run(case, n_devices, "xla")[steps]
    assert ref.keys() == xla.keys()
    for name in ref:
        assert ref[name].dtype == xla[name].dtype
        assert ref[name].tobytes() == xla[name].tobytes(), name


@pytest.mark.parametrize("n_devices", DEVICES)
def test_the_cases_exercise_what_they_name(n_devices):
    """Guards the parity test against comparing two idle systems: traffic
    flows, caps drop, the one-shard wiring starves the others, strays ride,
    the slots fold sees messages."""
    last = STEPS[-1]
    rand = _run("random", n_devices, "reference")[last]
    assert rand["state.received"].sum() > 0 and rand["dropped"].sum() == 0
    assert rand["inbox_valid"].sum() > 0
    assert _run("cap2", n_devices, "reference")[last]["dropped"].sum() > 0
    exact = _run("cap_exact", n_devices, "reference")
    # shard 0 drops the second half of its own run every step, and only it
    assert list(exact[1]["dropped"]) == [LOCAL_N] + [0] * (n_devices - 1)
    one = _run("to_one_shard", n_devices, "reference")[last]
    got = one["state.received"].reshape(n_devices, LOCAL_N).sum(axis=1)
    assert got[-1] > 0 and not got[:-1].any()
    assert _run("slots", n_devices, "reference")[last]["state.h"].any()
    assert _run("slots", n_devices, "reference")[last]["inbox_type"].any()


def test_cap_exact_keeps_the_first_rows_in_stable_order():
    """pair_cap == count_d exactly: shard 1's run for shard 0 survives whole
    and in emission order; of shard 0's own 2 * LOCAL_N rows the first
    LOCAL_N (every actor's lane 0 comes before its lane 1 only within the
    actor: rows are actor-major) survive."""
    snap = _run("cap_exact", 2, "reference")[1]
    m_local = snap["inbox_dst"].shape[0] // 2
    sender = snap["inbox_payload"][:m_local, 0]   # shard 0's inbox
    lane = snap["inbox_payload"][:m_local, 2]
    ok = snap["inbox_valid"][:m_local]
    from_0, from_1 = ok[:LOCAL_N], ok[LOCAL_N:2 * LOCAL_N]
    assert from_0.all() and from_1.all()
    # from shard 0 itself: actors 0..7, both lanes each, in that order
    assert list(sender[:LOCAL_N]) == [a for a in range(8) for _ in range(K)]
    assert list(lane[:LOCAL_N]) == [0, 1] * 8
    # from shard 1: every actor's lane 0, in actor order
    assert list(sender[LOCAL_N:2 * LOCAL_N]) == list(range(16, 32))
    assert not lane[LOCAL_N:2 * LOCAL_N].any()


def test_strays_ride_first():
    """In stray mode a forwarded inbox row outranks this step's emissions to
    the same shard: chunk rows start with the strays, in inbox order."""
    s = _build("stray", 2, "reference")
    s.run(1)
    s.run(1)
    s.enter_stray_mode()
    before = _snapshot(s)
    _misroute(s)
    moved = _snapshot(s)
    s.run(1)
    after = _snapshot(s)
    m_local = s.m_local
    # strays that sat in shard 0's inbox and now name shard 1's rows
    was = before["inbox_dst"][:m_local]
    now = moved["inbox_dst"][:m_local]
    strays = np.flatnonzero(moved["inbox_valid"][:m_local] & (now != was)
                            & (now >= LOCAL_N))
    assert strays.size
    # shard 1's inbox, the chunk that came from shard 0: strays first
    chunk = slice(m_local, m_local + s.pair_cap)
    np.testing.assert_array_equal(
        after["inbox_dst"][chunk][:strays.size], now[strays])
    np.testing.assert_array_equal(
        after["inbox_payload"][chunk][:strays.size],
        moved["inbox_payload"][:m_local][strays])
    assert after["inbox_valid"][chunk][:strays.size].all()


# ------------------------------------------------- the bucketing alone

def _model(dest, cols, fills, n_shards, pair_cap):
    """What a send buffer is: stable argsort by destination, each shard's
    rows cut at pair_cap, fill behind them."""
    order = np.argsort(dest, kind="stable")
    bufs = [np.full((n_shards * pair_cap,), f, c.dtype)
            for c, f in zip(cols, fills)]
    ok = np.zeros((n_shards * pair_cap,), np.bool_)
    dropped = 0
    for d in range(n_shards):
        rows = order[dest[order] == d]
        dropped += max(len(rows) - pair_cap, 0)
        rows = rows[:pair_cap]
        for buf, c in zip(bufs, cols):
            buf[d * pair_cap:d * pair_cap + len(rows)] = c[rows]
        ok[d * pair_cap:d * pair_cap + len(rows)] = True
    return bufs, ok, dropped


def _dest(kind, m, n_shards, rng):
    if kind == "random":          # some rows have nowhere to go
        return rng.integers(0, n_shards + 1, size=m)
    if kind == "nowhere":
        return np.full(m, n_shards)
    if kind == "all_to_last":     # every other run is empty
        return np.full(m, n_shards - 1)
    if kind == "all_to_first":
        return np.zeros(m, np.int64)
    if kind == "run_at_last_row":  # the last shard's run starts at row m - 1
        return np.r_[np.zeros(m - 1, np.int64), n_shards - 1]
    if kind == "ring":            # the mesh cell: everything to one neighbour
        return np.full(m, 1 % n_shards)
    raise ValueError(kind)


@pytest.mark.parametrize("m, n_shards, pair_cap, kind", [
    (64, 4, 64, "random"), (64, 4, 8, "random"), (64, 4, 100, "random"),
    (64, 4, 1, "random"), (65, 3, 7, "random"), (1, 2, 1, "random"),
    (64, 8, 64, "random"), (64, 1, 64, "random"), (64, 1, 5, "all_to_first"),
    (64, 4, 64, "nowhere"), (64, 4, 64, "all_to_last"),
    (64, 4, 16, "all_to_last"), (64, 4, 64, "all_to_first"),
    (64, 4, 64, "run_at_last_row"), (64, 8, 5, "run_at_last_row"),
    (64, 4, 64, "ring"), (64, 4, 63, "ring"), (256, 2, 128, "random"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_bucket_by_sort_against_the_numpy_model(m, n_shards, pair_cap, kind,
                                                dtype):
    rng = np.random.default_rng(m * 31 + n_shards * 7 + pair_cap)
    dest = _dest(kind, m, n_shards, rng).astype(np.int32)
    cols = (rng.integers(0, 1 << 20, size=m).astype(np.int32),
            rng.integers(0, 4, size=m).astype(np.int32),
            np.asarray(jnp.asarray(rng.integers(-50, 50, size=m), dtype)),
            np.asarray(jnp.asarray(rng.integers(-50, 50, size=m), dtype)))
    fills = (-1, 0, 0, 0)
    bufs, ok, dropped, counts = jax.jit(
        lambda d, *c: _bucket_by_sort(d, c, fills, n_shards, pair_cap))(
            jnp.asarray(dest), *map(jnp.asarray, cols))
    want, want_ok, want_dropped = _model(dest, cols, fills, n_shards,
                                         pair_cap)
    assert int(dropped) == want_dropped
    # what every chunk was asked to carry, before the cut at `pair_cap`
    assert [int(c) for c in counts] == np.bincount(
        dest, minlength=n_shards + 1)[:n_shards].tolist()
    np.testing.assert_array_equal(np.asarray(ok), want_ok)
    for got, exp in zip(bufs, want):
        assert got.dtype == exp.dtype and got.shape == exp.shape
        assert np.asarray(got).tobytes() == exp.tobytes()
