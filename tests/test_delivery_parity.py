"""Parity suite for the delivery kernels (ops/segment.py).

Reduce delivery has two kernels, scatter and the wide merge; each is held
to a float64 numpy segment oracle on integer-valued payloads kept inside
the prefix-difference envelope (docs/DELIVERY_KERNELS.md, "Exactness"), so
counts, maxes and sums are all exact. tests/test_delivery_compaction.py is
the bit-level lock on the merge kernel itself.

Slots delivery has two families behind the `delivery_backend` seam: the
ranked kernel (`_deliver_slots_ranked`) is a PERFORMANCE rewrite, the
wide-sort kernel is the semantic contract and what a TPU runs. Every field
of every SlotDelivery result must be bit-identical between them — not
approximately equal: float summation order is part of the contract (the
ranked kernel takes its cumsum over the rows in the wide kernel's sorted
order, at the same length, so XLA picks the same scan tree). These tests sweep dtypes,
M/N/P shapes, spill overflow, the drop bucket, and the rank strategies,
and pin the slots FIFO invariants against a numpy oracle.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from akka_tpu.ops import segment as sg

RNG = np.random.default_rng(20260805)


def _case(m, n, p, dtype=np.float32, frac_bad=0.15):
    dst = RNG.integers(-2, n + 2, size=m).astype(np.int32)  # strays included
    ok = RNG.random(m) > frac_bad
    if np.issubdtype(np.dtype(dtype) if dtype != jnp.bfloat16 else np.float32,
                     np.integer):
        payload = RNG.integers(-50, 50, size=(m, p)).astype(dtype)
        payload = jnp.asarray(payload)
    else:
        payload = jnp.asarray(
            RNG.standard_normal((m, p)).astype(np.float32)).astype(dtype)
    return jnp.asarray(dst), payload, jnp.asarray(ok)


def _assert_fields_identical(a, b, ctx):
    for f in a._fields:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert x.dtype == y.dtype, (ctx, f, x.dtype, y.dtype)
        assert np.array_equal(x, y), (
            f"{ctx}: field {f!r} differs between backends "
            f"(ref {x.ravel()[:8]} vs ranked {y.ravel()[:8]})")


# ---------------------------------------------------------------- reduce

REDUCE_SHAPES = [(257, 64, 3), (1024, 128, 4), (4096, 1000, 2),
                 (65, 7, 1), (5000, 16, 5), (33, 1, 2)]
REDUCE_KERNELS = ["scatter", "merge"]

# the largest column total |.| a dtype's prefix can carry exactly
_EXACT_PREFIX = {np.float32: 1 << 24, np.int32: 1 << 31, jnp.bfloat16: 1 << 8}


def _int_case(m, n, p, dtype=np.float32, frac_bad=0.15, rng=RNG):
    """Integer-valued payloads whose column totals of |value| stay under
    the dtype's exact range, so any summation order gives the same sums:
    values in [-50, 50], thinned to a few non-zero rows per column where
    the dtype is narrow. Strays and invalid rows included."""
    dst = rng.integers(-2, n + 2, size=m).astype(np.int32)
    ok = rng.random(m) > frac_bad
    vals = rng.integers(-50, 51, size=(m, p))
    keep = max(1, min(m, (_EXACT_PREFIX[dtype] - 1) // 50))
    if keep < m:
        thin = np.zeros((m, p), bool)
        for j in range(p):
            thin[rng.choice(m, size=keep, replace=False), j] = True
        vals = np.where(thin, vals, 0)
    return dst, vals, ok, jnp.asarray(vals.astype(np.float32)).astype(dtype)


def _segment_oracle(dst, vals, ok, n, need_max):
    """float64 numpy segment reduction: (sum [n, p], max [n, p], count
    [n]); empty segments read 0 in every field; max is zeros unless
    asked for."""
    live = ok & (dst >= 0) & (dst < n)
    p = vals.shape[1]
    sums = np.zeros((n, p), np.float64)
    np.add.at(sums, dst[live], vals[live].astype(np.float64))
    count = np.bincount(dst[live], minlength=n)[:n]
    maxs = np.zeros((n, p), np.float64)
    if need_max:
        maxs = np.full((n, p), -np.inf)
        np.maximum.at(maxs, dst[live], vals[live].astype(np.float64))
        maxs = np.where(count[:, None] > 0, maxs, 0.0)
    return sums, maxs, count


def _assert_matches_oracle(kernel, dst, vals, ok, payload, n, need_max, ctx):
    out = sg.deliver(jnp.asarray(dst), payload, jnp.asarray(ok), n,
                     need_max=need_max, mode=kernel)
    sums, maxs, count = _segment_oracle(dst, vals, ok, n, need_max)
    assert out.sum.dtype == out.max.dtype == payload.dtype, ctx
    assert out.count.dtype == jnp.int32, ctx
    np.testing.assert_array_equal(np.asarray(out.count), count, err_msg=ctx)
    np.testing.assert_array_equal(
        np.asarray(out.max).astype(np.float64), maxs, err_msg=ctx)
    np.testing.assert_array_equal(
        np.asarray(out.sum).astype(np.float64), sums, err_msg=ctx)


@pytest.mark.parametrize("m,n,p", REDUCE_SHAPES)
@pytest.mark.parametrize("kernel", REDUCE_KERNELS)
@pytest.mark.parametrize("need_max", [False, True])
def test_reduce_kernels_against_oracle(m, n, p, kernel, need_max):
    dst, vals, ok, payload = _int_case(m, n, p)
    _assert_matches_oracle(kernel, dst, vals, ok, payload, n, need_max,
                           f"reduce {kernel} m={m} n={n} p={p}")


@pytest.mark.parametrize("kernel", REDUCE_KERNELS)
@pytest.mark.parametrize("dtype,scale", [
    (np.float32, 1), (np.int32, 1), (jnp.bfloat16, 1),
    # every column's total of |value| between 2^24 and 2^31, the values odd
    # multiples: an f32 accumulator under an int32 column shows here alone
    pytest.param(np.int32, (1 << 15) + 1, id="int32-past-2^24")])
def test_reduce_kernels_against_oracle_dtypes(dtype, scale, kernel):
    dst, vals, ok, payload = _int_case(1024, 64, 4, dtype=dtype)
    vals, payload = vals * scale, payload * scale
    if scale > 1:
        totals = np.abs(vals[ok & (dst >= 0) & (dst < 64)]).sum(axis=0)
        assert ((1 << 24) < totals).all() and (totals < (1 << 31)).all()
    _assert_matches_oracle(kernel, dst, vals, ok, payload, 64, True,
                           f"reduce {kernel} dtype={dtype} x{scale}")


def test_reduce_kernels_all_invalid_and_all_one_actor():
    # drop-bucket edge: every row invalid or out of range
    dst = np.full(128, -1, np.int32)
    vals = RNG.integers(-50, 51, size=(128, 3))
    ok = np.zeros(128, bool)
    for kernel in REDUCE_KERNELS:
        _assert_matches_oracle(kernel, dst, vals, ok,
                               jnp.asarray(vals, jnp.float32), 8, True,
                               f"reduce {kernel} all-invalid")
    # the opposite extreme: every message on ONE hot actor (the whole
    # batch folds into a single segment)
    dst = np.full(4096, 3, np.int32)
    vals = RNG.integers(-50, 51, size=(4096, 4))
    ok = np.ones(4096, bool)
    for kernel in REDUCE_KERNELS:
        _assert_matches_oracle(kernel, dst, vals, ok,
                               jnp.asarray(vals, jnp.float32), 8, True,
                               f"reduce {kernel} one-hot-actor")


def test_stable_ranks_strategies_agree():
    """The packed single-operand rank strategy (cpu) and the 2-operand
    sort fallback must produce identical ranks/counts — the fallback is
    what TPU/GPU and the packing-overflow guard run."""
    for m, n in [(257, 16), (1024, 64), (65, 1), (4096, 1000)]:
        key = jnp.asarray(RNG.integers(0, n + 1, size=m).astype(np.int32))
        r_cpu, c_cpu = sg.stable_ranks(key, n, platform="cpu")
        r_gen, c_gen = sg.stable_ranks(key, n, platform="tpu")
        np.testing.assert_array_equal(np.asarray(r_cpu), np.asarray(r_gen))
        np.testing.assert_array_equal(np.asarray(c_cpu), np.asarray(c_gen))


# ---------------------------------------------------------------- slots

SLOT_CASES = [
    dict(m=257, n=16, p=3, slots=2, cap=0, kind=False, susp=False),
    dict(m=1024, n=64, p=4, slots=3, cap=64, kind=False, susp=False),
    dict(m=2048, n=32, p=2, slots=2, cap=16, kind=True, susp=True),
    dict(m=4096, n=100, p=4, slots=1, cap=8, kind=True, susp=True),
    dict(m=333, n=8, p=1, slots=4, cap=4, kind=True, susp=True),  # overflow
    dict(m=96, n=96, p=2, slots=2, cap=8, kind=True, susp=False),
]


@pytest.mark.parametrize("case", SLOT_CASES,
                         ids=[f"m{c['m']}n{c['n']}cap{c['cap']}"
                              for c in SLOT_CASES])
@pytest.mark.parametrize("need_max", [False, True])
def test_slots_parity(case, need_max):
    m, n, p, slots, cap = (case["m"], case["n"], case["p"], case["slots"],
                           case["cap"])
    dst, payload, ok = _case(m, n, p)
    mtype = jnp.asarray(RNG.integers(1, 5, size=m).astype(np.int32))
    kind = jnp.asarray(RNG.random(n) > 0.5) if case["kind"] else None
    susp = jnp.asarray(RNG.random(n) > 0.7) if case["susp"] else None
    ref = sg.deliver_slots(dst, mtype, payload, ok, n, slots,
                           need_max=need_max, spill_cap=cap,
                           slots_kind=kind, suspended=susp,
                           backend="reference")
    new = sg.deliver_slots(dst, mtype, payload, ok, n, slots,
                           need_max=need_max, spill_cap=cap,
                           slots_kind=kind, suspended=susp, backend="xla")
    _assert_fields_identical(ref, new, f"slots {case}")


def _queue_oracle(dst, mtype, vals, ok, n, slots, cap, kind, susp, need_max):
    """The literal queue rule, one message at a time in arrival order: a
    mailbox takes its first `slots` messages; with a spill region a
    suspended row keeps everything and a slots-kind row what is past its
    slots, actor-major in arrival order, cut at `cap`; a reduce-kind row
    consumes all of it. Returns every field of SlotDelivery as numpy."""
    m, p = vals.shape
    boxes = [[] for _ in range(n)]
    for i in range(m):
        if ok[i] and 0 <= dst[i] < n:
            boxes[dst[i]].append(i)
    types = np.zeros((n, slots), np.int32)
    payload = np.zeros((n, slots, p), vals.dtype)
    valid = np.zeros((n, slots), bool)
    count = np.zeros(n, np.int32)
    sums = np.zeros((n, p), vals.dtype)
    maxs = np.zeros((n, p), vals.dtype)
    kept, dropped = [], 0
    for a, rows in enumerate(boxes):
        if cap > 0 and susp is not None and susp[a]:
            kept += rows
            continue
        for r, i in enumerate(rows[:slots]):
            types[a, r], payload[a, r], valid[a, r] = mtype[i], vals[i], True
        eaten = rows
        if cap > 0 and (kind is None or kind[a]):
            eaten, kept = rows[:slots], kept + rows[slots:]
        elif cap == 0:
            dropped += len(rows[slots:])
        count[a] = len(eaten)
        sums[a] = vals[eaten].sum(axis=0) if eaten else 0
        if need_max and rows:  # a row left for later counts as a 0
            maxs[a] = np.where(np.isin(rows, eaten)[:, None], vals[rows],
                               0).max(axis=0)
    if cap > 0:
        dropped, kept = max(len(kept) - cap, 0), kept[:cap]
    fill = cap - len(kept)
    return sg.SlotDelivery(
        types=types, payload=payload, valid=valid, count=count, sum=sums,
        max=maxs, dropped=np.int32(dropped),
        spill_dst=np.r_[dst[kept], np.full(fill, -1)].astype(np.int32),
        spill_type=np.r_[mtype[kept], np.zeros(fill)].astype(np.int32),
        spill_payload=np.concatenate([vals[kept], np.zeros((fill, p))]
                                     ).astype(vals.dtype),
        spill_valid=np.arange(cap) < len(kept))


def _enqueue_case(name, m, n, p=2, slots=2, cap=0, dtype=np.float32,
                  dst="random", kind=None, susp=None, ranked=True,
                  overflows=None):
    return pytest.param(dict(m=m, n=n, p=p, slots=slots, cap=cap, dtype=dtype,
                             dst=dst, kind=kind, susp=susp, ranked=ranked,
                             overflows=overflows), id=name)


# the enqueue of the wide family (ISSUE 35, 37): what the sorted rows' shifted
# views, the heads' compress-and-expand, the flags' way back along it and the
# spill's compress must get right, each at its edge
ENQUEUE_CASES = [
    # the ranked family gathers from an empty array at m = 0: wide alone
    _enqueue_case("m0", 0, 5, ranked=False),
    _enqueue_case("m0-spill", 0, 5, cap=4, kind="mixed", ranked=False),
    # fewer messages than slots, all for actor 0: a shift longer than the
    # column must not read its own fill as key 0
    _enqueue_case("m-below-slots-key0", 3, 4, slots=16, dst=0),
    _enqueue_case("m-below-slots", 5, 9, p=4, slots=16, cap=8, kind="mixed"),
    _enqueue_case("one-mailbox-takes-all", 300, 7, cap=512, dst=3),
    _enqueue_case("one-mailbox-overflows-spill", 300, 7, cap=8, dst=3,
                  overflows=True),
    _enqueue_case("last-actor-takes-all", 130, 6, slots=16, cap=16, dst=5,
                  overflows=True),
    _enqueue_case("all-suspended", 40, 6, cap=64, susp="all"),
    _enqueue_case("all-suspended-overflows", 90, 6, cap=16, susp="all",
                  overflows=True),
    _enqueue_case("spill-cap-0-drops", 200, 5, slots=2, cap=0,
                  overflows=True),
    _enqueue_case("kinds-mixed", 400, 23, p=4, slots=3, cap=32, kind="mixed",
                  susp="some"),
    _enqueue_case("kinds-mixed-overflows", 400, 11, slots=2, cap=8,
                  kind="mixed", susp="some", overflows=True),
    _enqueue_case("slots1-p1", 257, 16, p=1, slots=1, cap=64),
    _enqueue_case("slots2-p4", 257, 16, p=4, slots=2, cap=64, kind="mixed"),
    _enqueue_case("slots16-p4", 600, 40, p=4, slots=16, cap=64, kind="mixed",
                  susp="some"),
    _enqueue_case("slots16-p1-more-actors-than-rows", 64, 300, p=1, slots=16,
                  cap=4),
    _enqueue_case("one-actor", 50, 1, slots=16, cap=64),
    _enqueue_case("bf16", 60, 12, p=4, slots=2, cap=16, dtype=jnp.bfloat16,
                  kind="mixed"),
    _enqueue_case("int32", 300, 12, p=4, slots=16, cap=16, dtype=np.int32,
                  kind="mixed", susp="some"),
    _enqueue_case("all-in-the-drop-bucket", 33, 4, cap=8, dst=-1),
    # the recipients' flags routed to the sorted rows and not gathered (ISSUE
    # 37). "others" flags every row that is sent nothing: one such flag at
    # the dense front would retain or spill a recipient's mail. A tuple
    # names the rows that are told, strays among them
    _enqueue_case("flags-of-rows-without-mail", 200, 64, cap=64,
                  dst=(3, 4, 17, 40, 63, -1, 64), kind="others",
                  susp="others"),
    _enqueue_case("flags-few-recipients-mixed", 300, 64, p=4, slots=3, cap=64,
                  dst=(0, 9, 10, 11, 62, -1, 64), kind="mixed", susp="some"),
    _enqueue_case("flags-only-row-0", 40, 9, cap=64, dst=(0, -1, 9),
                  kind="others", susp="others"),
    _enqueue_case("flags-only-row-0-suspended", 40, 9, cap=64, dst=0,
                  kind="mixed", susp="all"),
    _enqueue_case("flags-only-last-row", 40, 9, cap=64, dst=(8, 9, -1),
                  kind="others", susp="others"),
    _enqueue_case("flags-only-last-row-of-many", 12, 300, cap=8, dst=299,
                  kind="all", susp="others", overflows=True),
    _enqueue_case("flags-every-row-receives", 300, 25, p=4, slots=3, cap=64,
                  dst="every", kind="mixed", susp="some"),
    _enqueue_case("flags-fewer-rows-than-actors", 20, 300, slots=3, cap=16,
                  kind="mixed", susp="some"),
    _enqueue_case("flags-suspended-alone", 257, 16, slots=3, cap=128,
                  susp="some"),
    _enqueue_case("flags-kind-alone-none-takes-slots", 200, 7, cap=8,
                  kind="none"),
    _enqueue_case("flags-given-spill-cap-0", 200, 5, cap=0, kind="mixed",
                  susp="some", overflows=True),
]


@pytest.mark.parametrize("case", ENQUEUE_CASES)
def test_slots_enqueue_against_queue_oracle(case):
    """The wide family (what a TPU runs: slots and spill by shift-and-select
    routings of the sorted rows) against the literal queue and against the
    ranked family, every field raw-bit equal. A quarter of the rows are
    invalid or out of range and stand between the live ones."""
    c = case
    m, n, p, slots, cap = c["m"], c["n"], c["p"], c["slots"], c["cap"]
    rng = np.random.default_rng(35 + 7 * m + n)
    dst = (rng.integers(-1, n + 1, size=m) if c["dst"] == "random"
           else rng.permutation(m) % n if c["dst"] == "every"
           else rng.choice(np.atleast_1d(c["dst"]), size=m)).astype(np.int32)
    ok = rng.random(m) > 0.15
    mtype = rng.integers(0, 5, size=m).astype(np.int32)
    small = c["dtype"] == jnp.bfloat16  # every running sum inside 2^8
    vals = rng.integers(-3 if small else -50, 4 if small else 51, size=(m, p))
    payload = jnp.asarray(vals.astype(np.float32)).astype(c["dtype"])
    flag = {None: lambda: None, "all": lambda: np.ones(n, bool),
            "none": lambda: np.zeros(n, bool),
            "mixed": lambda: rng.random(n) > 0.5,
            "some": lambda: rng.random(n) > 0.7,
            "others": lambda: ~np.isin(np.arange(n), dst)}
    kind, susp = flag[c["kind"]](), flag[c["susp"]]()
    need_max = bool((m + n) % 2)

    def run(backend):
        return sg.deliver_slots(
            jnp.asarray(dst), jnp.asarray(mtype), payload, jnp.asarray(ok), n,
            slots, need_max=need_max, spill_cap=cap,
            slots_kind=None if kind is None else jnp.asarray(kind),
            suspended=None if susp is None else jnp.asarray(susp),
            backend=backend)

    want = _queue_oracle(dst, mtype, np.asarray(payload), ok, n, slots, cap,
                         kind, susp, need_max)
    wide = run("reference")
    for f in wide._fields:
        got, exp = np.asarray(getattr(wide, f)), getattr(want, f)
        assert got.dtype == exp.dtype and got.shape == exp.shape, (f, got, exp)
        assert got.tobytes() == exp.tobytes(), (f, got, exp)
    if c["overflows"] is not None:
        assert (int(want.dropped) > 0) == c["overflows"]
    if c["ranked"]:
        ranked = run("xla")
        for f in wide._fields:
            assert (np.asarray(getattr(wide, f)).tobytes()
                    == np.asarray(getattr(ranked, f)).tobytes()), f


@pytest.mark.parametrize("seed", range(12))
def test_flags_routed_to_the_sorted_rows_read_what_a_gather_reads(seed):
    """The routing that took the gather's place, alone: on every live
    sorted row `_flags_to_heads` and `_run_starts` give exactly
    ``flags[dst]`` and the run start of the plain `lax.cummax`, whatever
    the sizes (m above and below n), whoever receives (a few rows, row 0
    alone, the last row alone, every row, nobody) and with strays in the
    drop bucket."""
    rng = np.random.default_rng(3700 + seed)
    m, n = int(rng.integers(1, 700)), int(rng.integers(1, 400))
    told = [np.arange(n), rng.choice(n, size=min(n, 5)), np.array([0]),
            np.array([n - 1]), np.array([n])][seed % 5]
    dst = rng.choice(np.r_[told, n] if seed % 2 else told, size=m)
    if seed % 5 == 0 and m >= n:
        dst = rng.permutation(m) % n  # every actor, all displacements 0
    flags = rng.integers(0, 4, size=n).astype(np.int32)
    skey = jnp.sort(jnp.asarray(dst, jnp.int32))
    head, _, from_front, mine, to_actor = sg._run_heads(skey, n)
    hflags = sg._flags_to_heads(jnp.asarray(flags), from_front, mine,
                                to_actor)
    start, got = sg._run_starts(head, hflags)
    plain, none = sg._run_starts(head)
    assert none is None
    skey, head, hflags = np.asarray(skey), np.asarray(head), np.asarray(hflags)
    live = skey < n
    np.testing.assert_array_equal(np.asarray(start), np.asarray(plain))
    np.testing.assert_array_equal(
        np.asarray(plain), np.maximum.accumulate(
            np.where(head, np.arange(m), -1)))
    np.testing.assert_array_equal(np.asarray(got)[live],
                                  flags[np.clip(skey, 0, n - 1)][live])
    # a head holds its recipient's bits, every other row nothing
    np.testing.assert_array_equal(
        hflags, np.where(head & live, flags[np.clip(skey, 0, n - 1)], 0))


def test_max_of_a_segment_holding_only_the_lowest_value_is_that_value():
    """An empty segment reads max 0 because nothing was delivered, not
    because a sentinel was seen: a segment whose every message carries the
    dtype's lowest value reads that value, in both slots families and both
    reduce kernels."""
    low = np.iinfo(np.int32).min
    dst = jnp.asarray([2, 2, 0], jnp.int32)
    payload = jnp.asarray([[low], [low], [7]], jnp.int32)
    ok = jnp.ones((3,), jnp.bool_)
    want = np.array([[7], [0], [low], [0]], np.int32)
    for mode in REDUCE_KERNELS:
        got = sg.deliver(dst, payload, ok, 4, need_max=True, mode=mode)
        np.testing.assert_array_equal(np.asarray(got.max), want, mode)
    for backend in ("reference", "xla"):
        got = sg.deliver_slots(dst, dst, payload, ok, 4, 2, need_max=True,
                               backend=backend)
        np.testing.assert_array_equal(np.asarray(got.max), want, backend)


def test_slots_spill_overflow_drops_counted_identically():
    """Force more spill demand than spill_cap: the overflow count and the
    retained prefix must match the reference exactly (spill region order is
    actor-major, FIFO within actor)."""
    m, n, p, slots, cap = 512, 4, 2, 1, 8  # ~128 msgs/actor, 1 slot, cap 8
    dst = jnp.asarray(RNG.integers(0, n, size=m).astype(np.int32))
    payload = jnp.asarray(RNG.standard_normal((m, p)).astype(np.float32))
    ok = jnp.asarray(np.ones(m, bool))
    mtype = jnp.asarray(np.ones(m, np.int32))
    kind = jnp.asarray(np.ones(n, bool))  # every actor spills its overflow
    ref = sg.deliver_slots(dst, mtype, payload, ok, n, slots,
                           spill_cap=cap, slots_kind=kind,
                           backend="reference")
    new = sg.deliver_slots(dst, mtype, payload, ok, n, slots,
                           spill_cap=cap, slots_kind=kind, backend="xla")
    _assert_fields_identical(ref, new, "slots spill-overflow")
    assert int(np.asarray(new.dropped)) > 0  # the case really overflowed


def test_slots_fifo_oracle_ranked():
    """Ranked slots delivery against a plain-python oracle: per-actor FIFO
    (arrival order) in the mailbox slots, consumed counts, and sums."""
    m, n, p, slots = 400, 13, 3, 4
    dst = RNG.integers(0, n, size=m).astype(np.int32)
    mtype = RNG.integers(1, 5, size=m).astype(np.int32)
    payload = RNG.standard_normal((m, p)).astype(np.float32)
    ok = RNG.random(m) > 0.1
    out = sg.deliver_slots(jnp.asarray(dst), jnp.asarray(mtype),
                           jnp.asarray(payload), jnp.asarray(ok), n, slots,
                           need_max=True, backend="xla")
    types, pl = np.asarray(out.types), np.asarray(out.payload)
    vv, counts = np.asarray(out.valid), np.asarray(out.count)
    for a in range(n):
        idx = [i for i in range(m) if ok[i] and dst[i] == a]
        assert counts[a] == len(idx)
        for j in range(slots):
            if j < min(len(idx), slots):
                assert vv[a, j]
                assert types[a, j] == mtype[idx[j]]
                np.testing.assert_array_equal(pl[a, j], payload[idx[j]])
            else:
                assert not vv[a, j]


# ------------------------------------------- counting-sort rank family

COUNT_SHAPES = [(257, 16), (1024, 64), (64, 1), (96, 96), (4096, 1000),
                (33, 3), (333, 8)]


def test_counting_ranks_all_strategies_identical():
    """The counting strategy must be bit-identical to the packed sort and
    the 2-operand fallback across the shape sweep — same ranks, same
    counts, including the drop bucket (keys == n)."""
    for m, n in COUNT_SHAPES:
        key = jnp.asarray(RNG.integers(0, n + 1, size=m).astype(np.int32))
        outs = {s: sg.stable_ranks(key, n, platform="cpu", strategy=s)
                for s in ("counting", "packed", "sort2")}
        r0, c0 = outs["counting"]
        for s in ("packed", "sort2"):
            np.testing.assert_array_equal(
                np.asarray(r0), np.asarray(outs[s][0]),
                err_msg=f"ranks counting vs {s} m={m} n={n}")
            np.testing.assert_array_equal(
                np.asarray(c0), np.asarray(outs[s][1]),
                err_msg=f"counts counting vs {s} m={m} n={n}")


def test_counting_ranks_empty_segments_and_all_invalid():
    # sparse keys: the vast majority of recipients receive nothing
    n, m = 300, 513
    vals = np.array([0, 7, 299], np.int32)
    key = jnp.asarray(vals[RNG.integers(0, 3, size=m)])
    r_c, c_c = sg.stable_ranks(key, n, platform="cpu", strategy="counting")
    r_s, c_s = sg.stable_ranks(key, n, platform="cpu", strategy="sort2")
    np.testing.assert_array_equal(np.asarray(r_c), np.asarray(r_s))
    np.testing.assert_array_equal(np.asarray(c_c), np.asarray(c_s))
    assert int((np.asarray(c_c) == 0).sum()) >= n - 3
    # every row in the drop bucket (key == n): ranks are pure arrival
    # order, every real recipient's count is zero
    key = jnp.asarray(np.full(160, 12, np.int32))
    r_c, c_c = sg.stable_ranks(key, 12, platform="cpu", strategy="counting")
    np.testing.assert_array_equal(np.asarray(r_c), np.arange(160))
    c_c = np.asarray(c_c)
    assert c_c[12] == 160 and not c_c[:12].any()


def test_counting_ranks_forced_multi_pass():
    """A tiny max_bins forces the LSD decomposition through many 1-bit
    passes (inter-pass key permute + gather composition) — the result
    must not change."""
    m, n = 777, 1000
    key = jnp.asarray(RNG.integers(0, n + 1, size=m).astype(np.int32))
    r_1, c_1 = sg.counting_ranks(key, n)
    r_mp, c_mp = sg.counting_ranks(key, n, max_bins=64)
    r_p, c_p = sg.stable_ranks(key, n, platform="cpu", strategy="packed")
    np.testing.assert_array_equal(np.asarray(r_1), np.asarray(r_mp))
    np.testing.assert_array_equal(np.asarray(c_1), np.asarray(c_mp))
    np.testing.assert_array_equal(np.asarray(r_1), np.asarray(r_p))
    np.testing.assert_array_equal(np.asarray(c_1), np.asarray(c_p))


def test_counting_ranks_packing_overflow_boundary():
    """(n_keys + 2) * ceil(M/B) >= 2^31: the packed strategy's int32
    packing is illegal here, auto must route to counting, an explicit
    "packed" request must be rerouted too, and the ranks must still match
    the 2-operand fallback bit-for-bit."""
    m, n = (1 << 16) + 33, 1 << 20
    assert sg._auto_rank_strategy(m, n, "cpu") == "counting"
    key = jnp.asarray(RNG.integers(0, n + 1, size=m).astype(np.int32))
    r_a, c_a = sg.stable_ranks(key, n, platform="cpu")          # auto
    r_p, c_p = sg.stable_ranks(key, n, platform="cpu",
                               strategy="packed")               # rerouted
    r_s, c_s = sg.stable_ranks(key, n, platform="cpu", strategy="sort2")
    np.testing.assert_array_equal(np.asarray(r_a), np.asarray(r_s))
    np.testing.assert_array_equal(np.asarray(c_a), np.asarray(c_s))
    np.testing.assert_array_equal(np.asarray(r_p), np.asarray(r_s))
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_s))


def test_delivery_parity_with_counting_ranks(monkeypatch):
    """deliver_slots with the rank phase FORCED to counting stays
    bit-identical to the wide reference kernel (fresh shapes, so no cached
    packed trace is reused)."""
    monkeypatch.setattr(sg, "_auto_rank_strategy",
                        lambda m, n, platform: "counting")
    dst, payload, ok = _case(517, 29, 3)
    mtype = jnp.asarray(RNG.integers(1, 5, size=517).astype(np.int32))
    ref = sg.deliver_slots(dst, mtype, payload, ok, 29, 2, need_max=True,
                           spill_cap=8, backend="reference")
    new = sg.deliver_slots(dst, mtype, payload, ok, 29, 2, need_max=True,
                           spill_cap=8, backend="xla")
    _assert_fields_identical(ref, new, "counting slots")
    # all-invalid through the full delivery with counting ranks
    dead = jnp.asarray(np.zeros(517, bool))
    ref = sg.deliver_slots(dst, mtype, payload, dead, 29, 2,
                           backend="reference")
    new = sg.deliver_slots(dst, mtype, payload, dead, 29, 2, backend="xla")
    _assert_fields_identical(ref, new, "counting slots all-invalid")


def test_backend_seam_roundtrip():
    """Every backend name resolves to its family on both platforms, for
    the slots kernel and for the exchange bucketing alike."""
    want = {(None, "cpu"): "ranked", (None, "tpu"): "wide",
            ("auto", "cpu"): "ranked", ("auto", "tpu"): "wide",
            ("xla", "cpu"): "ranked", ("xla", "tpu"): "ranked",
            ("reference", "cpu"): "wide", ("reference", "tpu"): "wide"}
    assert {b for b, _ in want} == {None, *sg.DELIVERY_BACKENDS}
    for (backend, platform), family in want.items():
        assert sg._backend_impl(backend, platform) == family
        assert sg.exchange_uses_ranked(platform, backend) == (
            family == "ranked")


@pytest.mark.parametrize("where", ["per-call", "per-system"])
def test_unknown_delivery_backend_raises(where):
    """A typo must not silently fall back to `auto`: the name is checked
    where it is resolved (deliver_slots, the exchange) and where a system
    takes it, reduce-mode systems included."""
    from akka_tpu.batched import Emit, behavior
    from akka_tpu.batched.core import BatchedSystem

    if where == "per-call":
        dst, payload, ok = _case(33, 4, 2)
        with pytest.raises(ValueError, match="refrence"):
            sg.deliver_slots(dst, dst, payload, ok, 4, 2, backend="refrence")
        with pytest.raises(ValueError, match="pallas"):
            sg.exchange_uses_ranked("tpu", "pallas")
        return

    @behavior("noop-seam", {})
    def noop(state, inbox, ctx):
        return {}, Emit.none(1, 4)

    with pytest.raises(ValueError, match="refrence"):
        BatchedSystem(8, [noop], payload_width=4,
                      delivery_backend="refrence")
