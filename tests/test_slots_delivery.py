"""The ordered-mailbox enqueue (ops/segment.py `deliver_slots`) against one
literal queue, case by case, on both kernel families.

`deliver_slots` returns (delivery, place, retain) on either family: the
wide kernel (every column rides one sort, the slots routed off the sorted
rows: what a TPU runs) and the ranked one (a narrow key sort, then closed-
form gathers: a CPU's `auto`). Each answers to `literal_queue`, a plain
numpy replay of the mailbox rule, every field raw-bit equal: the slots of
every row and of the rows below the extent (`place`), `valid`, `count`,
`sum`, `max`, `dropped`, the spill region, and with retention what
`retain(taken)` spills and keeps. Payloads are integer-valued and inside
the prefix envelope (docs/DELIVERY_KERNELS.md, "Exactness"), so any
summation order gives the same sums. Each case draws its inputs from a
generator seeded by its own id, and compiles once a family: the delivery,
both `place` widths and `retain` are one program (`one_program`).

Where the payloads are floats the families must still agree bit for bit:
the ranked kernel takes its prefix sums over the rows in the wide kernel's
sorted order, at the same length (`test_the_families_sum_floats_alike`).

A change to `deliver_slots` adds its cases to `CASES`."""

import functools
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from akka_tpu.ops import segment as sg

FAMILIES = {"wide": "reference", "ranked": "xla"}


def one_program(fn, fuse=False):
    """`fn` compiled as one program, by default without XLA's fusion pass,
    which takes most of the compile of the wide kernel's many routings at
    toy size: every operation is then compiled as it would be alone, as
    when it runs eagerly, so a float's association is the kernel's own and
    not the fuser's choice."""
    return jax.jit(fn, compiler_options=None if fuse else {
        "xla_disable_hlo_passes": "fusion"})


def literal_queue(dst, mtype, vals, ok, n, slots, cap, kind, susp, need_max,
                  retain=False, taken=None):
    """The literal queue rule, one message at a time in arrival order: a
    mailbox takes its first `slots` messages; with a spill region a
    suspended row keeps everything and a slots-kind row what is past its
    slots, actor-major in arrival order, cut at `cap`; a reduce-kind row
    consumes all of it; without one, what passes `slots` is lost. Returns
    (delivery, types [n, slots], payload [n, slots, p], retained): the
    delivery as `deliver_slots` gives it (its spill None with `retain`),
    and with `retain` what `retain(taken)` gives, (spill, lost, kept)."""
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
    spilled, left, dropped, kept = [], [], 0, 0
    for a, rows in enumerate(boxes):
        if cap > 0 and susp is not None and susp[a]:
            spilled, left = spilled + rows, left + rows
            continue
        for r, i in enumerate(rows[:slots]):
            types[a, r], payload[a, r], valid[a, r] = mtype[i], vals[i], True
        eaten = rows
        if cap > 0 and (kind is None or kind[a]):
            eaten, spilled = rows[:slots], spilled + rows[slots:]
            if retain:  # the fold handled the first `taken` slots
                left += rows[taken[a]:]
                kept += max(min(len(rows), slots) - taken[a], 0)
        elif cap == 0:
            dropped += len(rows[slots:])
        count[a] = len(eaten)
        sums[a] = vals[eaten].sum(axis=0) if eaten else 0
        if need_max and rows:  # a row left for later counts as a 0
            maxs[a] = np.where(np.isin(rows, eaten)[:, None], vals[rows],
                               0).max(axis=0)

    def region(rows):
        rows, fill = rows[:cap], max(cap - len(rows), 0)
        return (np.r_[dst[rows], np.full(fill, -1)].astype(np.int32),
                np.r_[mtype[rows], np.zeros(fill)].astype(np.int32),
                np.concatenate([vals[rows], np.zeros((fill, p))]
                               ).astype(vals.dtype),
                np.arange(cap) < len(rows))

    spill = (None,) * 4 if retain else region(spilled)
    if cap > 0:
        dropped = 0 if retain else max(len(spilled) - cap, 0)
    delivery = sg.SlotDelivery(
        valid, count, sums, maxs, np.int32(dropped), *spill)
    retained = ((region(left), np.int32(max(len(left) - cap, 0)),
                 np.int32(kept)) if retain else None)
    return delivery, types, payload, retained


def case(name, m, n, p=2, slots=2, cap=0, dtype=np.float32, dst="random",
         kind=None, susp=None, need_max=None, extent=None, retain=False,
         overflows=None, inbox=None):
    """One row of the table. `dst`: "random" (strays past both ends),
    "live" (actors only), "every" (each actor in turn), "past" (three in
    ten below the extent), "half" (half below it, then random), or the
    rows told (one, or a tuple, strays among them). `kind` / `susp`: a
    share of the actors, "all", "none", or "others" (those told nothing).
    `overflows`: whether mail is lost. `inbox`: the messages as given,
    (dst, mtype, payload, valid)."""
    return pytest.param(dict(
        m=m, n=n, p=p, slots=slots, cap=cap, dtype=dtype, dst=dst, kind=kind,
        susp=susp, need_max=bool((m + n) % 2) if need_max is None
        else need_max, extent=extent, retain=retain, overflows=overflows,
        inbox=inbox), id=name)


CASES = [
    # the wide enqueue's routings, each at its edge: the sorted rows'
    # shifted views, the heads' compress-and-expand, the flags' way back
    # along it and the spill's compress
    case("m0", 0, 5),
    case("m0-spill", 0, 5, cap=4, kind=0.5),
    # fewer messages than slots, all for actor 0: a shift longer than the
    # column must not read its own fill as key 0
    case("m-below-slots-key0", 3, 4, slots=16, dst=0),
    case("m-below-slots", 5, 9, p=4, slots=16, cap=8, kind=0.5),
    case("one-mailbox-takes-all", 300, 7, cap=512, dst=3),
    case("one-mailbox-overflows-spill", 300, 7, cap=8, dst=3, retain=True,
         overflows=True),
    case("last-actor-takes-all", 130, 6, slots=16, cap=16, dst=5,
         overflows=True),
    case("all-suspended", 40, 6, cap=64, susp="all"),
    case("all-suspended-overflows", 90, 6, cap=16, susp="all",
         overflows=True),
    case("spill-cap-0-drops", 200, 5, slots=2, cap=0, overflows=True),
    case("kinds-mixed", 400, 23, p=4, slots=3, cap=32, kind=0.5, susp=0.3,
         retain=True),
    case("kinds-mixed-overflows", 400, 11, slots=2, cap=8, kind=0.5,
         susp=0.3, overflows=True),
    case("slots1-p1", 257, 16, p=1, slots=1, cap=64),
    case("slots2-p4", 257, 16, p=4, slots=2, cap=64, kind=0.5),
    case("slots16-p4", 600, 40, p=4, slots=16, cap=64, kind=0.5, susp=0.3),
    case("slots16-p1-more-actors-than-rows", 64, 300, p=1, slots=16, cap=4),
    case("one-actor", 50, 1, slots=16, cap=64),
    case("bf16", 60, 12, p=4, slots=2, cap=16, dtype=jnp.bfloat16,
         kind=0.5),
    case("int32", 300, 12, p=4, slots=16, cap=16, dtype=np.int32, kind=0.5,
         susp=0.3),
    case("all-in-the-drop-bucket", 33, 4, cap=8, dst=-1),
    # the recipients' flags routed to the sorted rows, not gathered.
    # "others" flags every row that is sent nothing: one such flag at the
    # dense front would retain or spill a recipient's mail
    case("flags-of-rows-without-mail", 200, 64, cap=64,
         dst=(3, 4, 17, 40, 63, -1, 64), kind="others", susp="others"),
    case("flags-few-recipients-mixed", 300, 64, p=4, slots=3, cap=64,
         dst=(0, 9, 10, 11, 62, -1, 64), kind=0.5, susp=0.3),
    case("flags-only-row-0", 40, 9, cap=64, dst=(0, -1, 9), kind="others",
         susp="others"),
    case("flags-only-row-0-suspended", 40, 9, cap=64, dst=0, kind=0.5,
         susp="all"),
    case("flags-only-last-row", 40, 9, cap=64, dst=(8, 9, -1),
         kind="others", susp="others"),
    case("flags-only-last-row-of-many", 16, 300, cap=8, dst=299, kind="all",
         susp="others", overflows=True),
    case("flags-every-row-receives", 300, 25, p=4, slots=3, cap=64,
         dst="every", kind=0.5, susp=0.3),
    case("flags-fewer-rows-than-actors", 20, 300, slots=3, cap=16, kind=0.5,
         susp=0.3),
    case("flags-suspended-alone", 257, 16, slots=3, cap=128, susp=0.3,
         retain=True),
    case("flags-kind-alone-none-takes-slots", 200, 7, cap=8, kind="none"),
    case("flags-given-spill-cap-0", 200, 5, cap=0, kind=0.5, susp=0.3,
         overflows=True),
    # the slots extent R: `place(R)` routes R rows' slots, `place(n)` every
    # row's (the `place_all` block); more inbox rows than slots and fewer,
    # S of 1, 4 and 16, a mailbox far over S, keys past the width
    case("more-rows-than-slots", 3000, 300, p=4, slots=4, cap=64, kind=0.7,
         susp=0.1, extent=96),
    case("fewer-rows-than-slots", 200, 300, p=4, slots=16, cap=64, kind=0.7,
         susp=0.1, extent=128),
    case("one-slot", 700, 500, p=4, slots=1, cap=32, kind=0.7, extent=128),
    case("empty-inbox", 0, 64, p=4, slots=4, cap=16, kind=0.7, susp=0.1,
         extent=32),
    case("every-message-to-one-actor", 150, 200, p=4, slots=16, cap=256,
         dtype=np.int32, dst=7, kind=0.7, extent=64),
    case("suspended-recipients", 1200, 256, p=4, slots=4, cap=512,
         kind=0.7, susp=0.5, extent=64),
    case("keys-past-the-width", 2500, 1000, p=4, slots=16, cap=128,
         dtype=np.int32, dst="past", kind=0.7, extent=64),
    case("no-spill-region", 900, 300, p=4, slots=4, cap=0, kind=0.7,
         susp=0.1, extent=128),
    case("spill", 1000, 300, p=4, slots=4, cap=64, dst="half", kind=0.8,
         susp=0.05, need_max=True, extent=128, retain=True),
    case("few-messages-no-spill", 50, 300, p=4, slots=3, dst="half",
         kind=0.8, susp=0.05, need_max=True, extent=128),
    case("more-heads-than-extent", 2000, 300, p=4, slots=4, cap=64,
         dst="half", kind=0.8, susp=0.05, need_max=True, extent=256),
    case("bank-like", 4500, 2048, p=4, slots=8, cap=128, dst="half",
         kind=0.8, susp=0.05, need_max=True, extent=512),
    # the mailbox rule spelt out: actor 0 is told three times (one lost);
    # an invalid row, a stray below and one above
    case("order-and-overflow", 6, 3, need_max=False, overflows=True,
         inbox=([0, 1, 0, 2, 0, 1], [10, 20, 11, 30, 12, 21],
                np.arange(6)[:, None] * np.ones((6, 2)), [True] * 6)),
    case("invalid-and-out-of-range", 4, 4, p=1, need_max=False,
         overflows=False, inbox=([0, -1, 7, 1], [1, 2, 3, 4], np.ones((4, 1)),
                                 [True, True, True, False])),
    # more spill demand than the region holds: what is kept and what is
    # counted lost, actor-major and in arrival order within an actor
    case("spill-overflow-drops-counted", 512, 4, slots=1, cap=8, dst="live",
         kind="all", overflows=True),
    case("fifo", 400, 13, p=3, slots=4, dst="live", need_max=True),
]


def inputs(c, rng):
    """(dst, mtype, payload, valid, kind, susp, taken) as numpy, the
    payload integer-valued in the case's dtype."""
    m, n, p, extent = c["m"], c["n"], c["p"], c["extent"]
    if c["inbox"] is not None:
        dst, mtype, vals, ok = map(np.asarray, c["inbox"])
    else:
        where = c["dst"]
        dst = {"random": lambda: rng.integers(-3, n + 3, m),
               "live": lambda: rng.integers(0, n, m),
               "every": lambda: rng.permutation(m) % n,
               "past": lambda: np.where(rng.random(m) < 0.3,
                                        rng.integers(0, extent, m),
                                        rng.integers(extent, n, m)),
               "half": lambda: np.r_[rng.integers(0, extent, m // 2),
                                     rng.integers(-5, n + 5, m - m // 2)]
               }.get(where, lambda: rng.choice(np.atleast_1d(where), m))()
        mtype = rng.integers(0, 9, m)
        ok = rng.random(m) > 0.15
        small = 3 if c["dtype"] == jnp.bfloat16 else 50  # the envelope
        vals = rng.integers(-small, small + 1, (m, p))
    dst = dst.astype(np.int32)

    def flag(how):
        if how is None or isinstance(how, float):
            return None if how is None else rng.random(n) < how
        return {"all": np.ones(n, bool), "none": np.zeros(n, bool),
                "others": ~np.isin(np.arange(n), dst)}[how]

    kind, susp = flag(c["kind"]), flag(c["susp"])
    payload = np.asarray(jnp.asarray(vals, jnp.float32).astype(c["dtype"]))
    taken = (rng.integers(0, c["slots"] + 1, n).astype(np.int32)
             if c["retain"] else None)
    return (dst, mtype.astype(np.int32), payload, ok.astype(bool), kind,
            susp, taken)


def delivered(c, backend, dst, mtype, payload, ok, kind, susp, taken):
    """One program: the delivery, `place` at every width the case asks
    for, and `retain(taken)`."""
    n = c["n"]
    widths = sorted({n, c["extent"] or n})

    # the ranked kernel compiles faster fused
    @functools.partial(one_program, fuse=backend == FAMILIES["ranked"])
    def run(dst, mtype, payload, ok, kind, susp, taken):
        d, place, retain = sg.deliver_slots(
            dst, mtype, payload, ok, n, c["slots"], need_max=c["need_max"],
            spill_cap=c["cap"], slots_kind=kind, suspended=susp,
            backend=backend, extent=c["extent"], retain=c["retain"])
        return (d, {w: place(w) for w in widths},
                None if retain is None else retain(taken))

    return run(dst, mtype, payload, ok, kind, susp, taken)


def same_bits(got, want, what):
    """Raw-bit equality of two pytrees of arrays, None standing for None."""
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want), what
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert (g.dtype, g.shape) == (w.dtype, w.shape), (what, g, w)
        assert g.tobytes() == w.tobytes(), (what, g, w)


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("c", CASES)
def test_deliver_slots_is_the_queue(c, family, request):
    rng = np.random.default_rng(zlib.crc32(request.node.callspec.id
                                           .rsplit("-", 1)[0].encode()))
    dst, mtype, payload, ok, kind, susp, taken = args = inputs(c, rng)
    got, placed, retained = delivered(c, FAMILIES[family], *args)
    want, types, want_payload, want_retained = literal_queue(
        dst, mtype, payload, ok, c["n"], c["slots"], c["cap"], kind, susp,
        c["need_max"], c["retain"], taken)
    for f in want._fields:
        same_bits(getattr(got, f), getattr(want, f), f)
    for width, slots in placed.items():
        same_bits(slots, (types[:width], want_payload[:width]),
                  f"place({width})")
    same_bits(retained, want_retained, "retain")
    if c["m"] and c["extent"]:
        assert types[:c["extent"]].any()  # something was placed below R
    if c["overflows"] is not None:
        lost = want.dropped + (want_retained[1] if c["retain"] else 0)
        assert (lost > 0) == c["overflows"]


# floats: the families' sums and maxima must agree bit for bit all the
# same (every other field moves values and never adds them). Fused, the CPU
# compiler may emit a prefix sum's dot in another order in one family than
# in the other (the one row of the 96-message case): `one_program` leaves
# each dot as it is alone
FLOAT_CASES = [
    dict(m=257, n=16, p=3, slots=2, cap=0, kind=False, susp=False),
    dict(m=1024, n=64, p=4, slots=3, cap=64, kind=False, susp=False),
    dict(m=2048, n=32, p=2, slots=2, cap=16, kind=True, susp=True),
    dict(m=4096, n=100, p=4, slots=1, cap=8, kind=True, susp=True),
    dict(m=333, n=8, p=1, slots=4, cap=4, kind=True, susp=True),  # overflow
    dict(m=96, n=96, p=2, slots=2, cap=8, kind=True, susp=False),
]


@pytest.mark.parametrize("need_max", [False, True])
@pytest.mark.parametrize("c", FLOAT_CASES,
                         ids=[f"m{c['m']}n{c['n']}cap{c['cap']}"
                              for c in FLOAT_CASES])
def test_the_families_sum_floats_alike(c, need_max, request):
    rng = np.random.default_rng(zlib.crc32(request.node.callspec.id.encode()))
    m, n = c["m"], c["n"]
    args = (jnp.asarray(rng.integers(-2, n + 2, m).astype(np.int32)),
            jnp.asarray(rng.integers(1, 5, m).astype(np.int32)),
            jnp.asarray(rng.standard_normal((m, c["p"])).astype(np.float32)),
            jnp.asarray(rng.random(m) > 0.15),
            jnp.asarray(rng.random(n) > 0.5) if c["kind"] else None,
            jnp.asarray(rng.random(n) > 0.7) if c["susp"] else None)

    def run(backend):
        @one_program
        def go(dst, mtype, payload, ok, kind, susp):
            d, _, _ = sg.deliver_slots(
                dst, mtype, payload, ok, n, c["slots"], need_max=need_max,
                spill_cap=c["cap"], slots_kind=kind, suspended=susp,
                backend=backend)
            return d.sum, d.max
        return go(*args)

    same_bits(run("reference"), run("xla"), c)


def test_a_slot_index_that_passes_an_int32_is_refused():
    """The wide kernel's key*S + rank is an int32: N*S of 2^31 or more
    raises before any work; just under it the kernel traces."""
    small = (jnp.zeros((8,), jnp.int32), jnp.zeros((8,), jnp.int32),
             jnp.zeros((8, 4), jnp.float32), jnp.ones((8,), jnp.bool_))
    wide = FAMILIES["wide"]
    with pytest.raises(ValueError, match="int32"):
        sg.deliver_slots(*small, 1 << 27, 16, spill_cap=8, backend=wide)
    with pytest.raises(ValueError, match="int32"):
        sg.deliver_slots(*small, 1 << 31, 1, backend=wide)
    types, payload = jax.eval_shape(
        lambda *a: sg.deliver_slots(*a, (1 << 27) - 1, 16, spill_cap=8,
                                    backend=wide, extent=1024)[1](1024),
        *small)
    assert types.shape == (1024, 16) and payload.shape == (1024, 16, 4)
