"""Parity suite for the delivery kernels (ops/segment.py).

Reduce delivery has two kernels, scatter and the wide merge; each is held
to a float64 numpy segment oracle on integer-valued payloads kept inside
the prefix-difference envelope (docs/DELIVERY_KERNELS.md, "Exactness"), so
counts, maxes and sums are all exact. tests/test_delivery_compaction.py is
the bit-level lock on the merge kernel itself.

Slots delivery's two families answer to one literal queue in
tests/test_slots_delivery.py. Here: the wide family's flag routing alone,
the rank strategies of the ranked family (each must give the ranks of the
others, and the delivery the wide kernel's, bit for bit), and the
`delivery_backend` names. Every test draws from a generator of its own.
"""

import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from akka_tpu.ops import segment as sg
from test_slots_delivery import one_program


@pytest.fixture
def rng(request):
    """A generator seeded by the test's own id."""
    return np.random.default_rng(zlib.crc32(request.node.name.encode()))


def _case(m, n, p, rng):
    dst = rng.integers(-2, n + 2, size=m).astype(np.int32)  # strays included
    ok = rng.random(m) > 0.15
    payload = rng.standard_normal((m, p)).astype(np.float32)
    return jnp.asarray(dst), jnp.asarray(payload), jnp.asarray(ok)


def _assert_families_identical(ctx, dst, mtype, payload, ok, n, slots, **kw):
    """Every field of both slots families' deliveries, and the slots of
    every row, bit for bit, each family one program."""
    def family(backend):
        @one_program
        def run(*args):
            d, place, _ = sg.deliver_slots(*args, n, slots, backend=backend,
                                           **kw)
            return d, place(n)
        return run(dst, mtype, payload, ok)

    wide, ranked = family("reference"), family("xla")
    for x, y in zip(jax.tree.leaves(wide), jax.tree.leaves(ranked)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype, (ctx, x.dtype, y.dtype)
        assert np.array_equal(x, y), (
            f"{ctx}: differs between backends "
            f"(ref {x.ravel()[:8]} vs ranked {y.ravel()[:8]})")


# ---------------------------------------------------------------- reduce

REDUCE_SHAPES = [(257, 64, 3), (1024, 128, 4), (4096, 1000, 2),
                 (65, 7, 1), (5000, 16, 5), (33, 1, 2)]
REDUCE_KERNELS = ["scatter", "merge"]

# the largest column total |.| a dtype's prefix can carry exactly
_EXACT_PREFIX = {np.float32: 1 << 24, np.int32: 1 << 31, jnp.bfloat16: 1 << 8}


def _int_case(m, n, p, rng, dtype=np.float32, frac_bad=0.15):
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
    out = jax.jit(lambda d, pl, v: sg.deliver(
        d, pl, v, n, need_max=need_max, mode=kernel))(
            jnp.asarray(dst), payload, jnp.asarray(ok))
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
def test_reduce_kernels_against_oracle(m, n, p, kernel, need_max, rng):
    dst, vals, ok, payload = _int_case(m, n, p, rng)
    _assert_matches_oracle(kernel, dst, vals, ok, payload, n, need_max,
                           f"reduce {kernel} m={m} n={n} p={p}")


@pytest.mark.parametrize("kernel", REDUCE_KERNELS)
@pytest.mark.parametrize("dtype,scale", [
    (np.float32, 1), (np.int32, 1), (jnp.bfloat16, 1),
    # every column's total of |value| between 2^24 and 2^31, the values odd
    # multiples: an f32 accumulator under an int32 column shows here alone
    pytest.param(np.int32, (1 << 15) + 1, id="int32-past-2^24")])
def test_reduce_kernels_against_oracle_dtypes(dtype, scale, kernel, rng):
    dst, vals, ok, payload = _int_case(1024, 64, 4, rng, dtype=dtype)
    vals, payload = vals * scale, payload * scale
    if scale > 1:
        totals = np.abs(vals[ok & (dst >= 0) & (dst < 64)]).sum(axis=0)
        assert ((1 << 24) < totals).all() and (totals < (1 << 31)).all()
    _assert_matches_oracle(kernel, dst, vals, ok, payload, 64, True,
                           f"reduce {kernel} dtype={dtype} x{scale}")


def test_reduce_kernels_all_invalid_and_all_one_actor(rng):
    # drop-bucket edge: every row invalid or out of range
    dst = np.full(128, -1, np.int32)
    vals = rng.integers(-50, 51, size=(128, 3))
    ok = np.zeros(128, bool)
    for kernel in REDUCE_KERNELS:
        _assert_matches_oracle(kernel, dst, vals, ok,
                               jnp.asarray(vals, jnp.float32), 8, True,
                               f"reduce {kernel} all-invalid")
    # the opposite extreme: every message on ONE hot actor (the whole
    # batch folds into a single segment)
    dst = np.full(4096, 3, np.int32)
    vals = rng.integers(-50, 51, size=(4096, 4))
    ok = np.ones(4096, bool)
    for kernel in REDUCE_KERNELS:
        _assert_matches_oracle(kernel, dst, vals, ok,
                               jnp.asarray(vals, jnp.float32), 8, True,
                               f"reduce {kernel} one-hot-actor")


def test_stable_ranks_strategies_agree(rng):
    """The packed single-operand rank strategy (cpu) and the 2-operand
    sort fallback must produce identical ranks/counts — the fallback is
    what TPU/GPU and the packing-overflow guard run."""
    for m, n in [(257, 16), (1024, 64), (65, 1), (4096, 1000)]:
        key = jnp.asarray(rng.integers(0, n + 1, size=m).astype(np.int32))
        r_cpu, c_cpu = sg.stable_ranks(key, n, platform="cpu")
        r_gen, c_gen = sg.stable_ranks(key, n, platform="tpu")
        np.testing.assert_array_equal(np.asarray(r_cpu), np.asarray(r_gen))
        np.testing.assert_array_equal(np.asarray(c_cpu), np.asarray(c_gen))


# ---------------------------------------------------------------- slots

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

    @jax.jit
    def routed(dst, flags):
        skey = jnp.sort(dst)
        head, from_front, mine, to_actor = sg._run_heads(skey, n)
        hflags = sg._flags_to_heads(flags, from_front, mine, to_actor)
        start, got = sg._run_starts(head, hflags)
        plain, none = sg._run_starts(head)
        assert none is None
        return skey, head, hflags, start, got, plain

    skey, head, hflags, start, got, plain = map(np.asarray, routed(
        jnp.asarray(dst, jnp.int32), jnp.asarray(flags)))
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
        got, _, _ = sg.deliver_slots(dst, dst, payload, ok, 4, 2,
                                     need_max=True, backend=backend)
        np.testing.assert_array_equal(np.asarray(got.max), want, backend)


# ------------------------------------------- counting-sort rank family

COUNT_SHAPES = [(257, 16), (1024, 64), (64, 1), (96, 96), (4096, 1000),
                (33, 3), (333, 8)]


def test_counting_ranks_all_strategies_identical(rng):
    """The counting strategy must be bit-identical to the packed sort and
    the 2-operand fallback across the shape sweep — same ranks, same
    counts, including the drop bucket (keys == n)."""
    for m, n in COUNT_SHAPES:
        key = jnp.asarray(rng.integers(0, n + 1, size=m).astype(np.int32))
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


def test_counting_ranks_empty_segments_and_all_invalid(rng):
    # sparse keys: the vast majority of recipients receive nothing
    n, m = 300, 513
    vals = np.array([0, 7, 299], np.int32)
    key = jnp.asarray(vals[rng.integers(0, 3, size=m)])
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


def test_counting_ranks_forced_multi_pass(rng):
    """A tiny max_bins forces the LSD decomposition through many 1-bit
    passes (inter-pass key permute + gather composition) — the result
    must not change."""
    m, n = 777, 1000
    key = jnp.asarray(rng.integers(0, n + 1, size=m).astype(np.int32))
    r_1, c_1 = sg.counting_ranks(key, n)
    r_mp, c_mp = sg.counting_ranks(key, n, max_bins=64)
    r_p, c_p = sg.stable_ranks(key, n, platform="cpu", strategy="packed")
    np.testing.assert_array_equal(np.asarray(r_1), np.asarray(r_mp))
    np.testing.assert_array_equal(np.asarray(c_1), np.asarray(c_mp))
    np.testing.assert_array_equal(np.asarray(r_1), np.asarray(r_p))
    np.testing.assert_array_equal(np.asarray(c_1), np.asarray(c_p))


def test_counting_ranks_packing_overflow_boundary(rng):
    """(n_keys + 2) * ceil(M/B) >= 2^31: the packed strategy's int32
    packing is illegal here, auto must route to counting, an explicit
    "packed" request must be rerouted too, and the ranks must still match
    the 2-operand fallback bit-for-bit."""
    m, n = (1 << 16) + 33, 1 << 20
    assert sg._auto_rank_strategy(m, n, "cpu") == "counting"
    key = jnp.asarray(rng.integers(0, n + 1, size=m).astype(np.int32))
    r_a, c_a = sg.stable_ranks(key, n, platform="cpu")          # auto
    r_p, c_p = sg.stable_ranks(key, n, platform="cpu",
                               strategy="packed")               # rerouted
    r_s, c_s = sg.stable_ranks(key, n, platform="cpu", strategy="sort2")
    np.testing.assert_array_equal(np.asarray(r_a), np.asarray(r_s))
    np.testing.assert_array_equal(np.asarray(c_a), np.asarray(c_s))
    np.testing.assert_array_equal(np.asarray(r_p), np.asarray(r_s))
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_s))


def test_delivery_parity_with_counting_ranks(monkeypatch, rng):
    """deliver_slots with the rank phase FORCED to counting stays
    bit-identical to the wide reference kernel (fresh shapes, so no cached
    packed trace is reused)."""
    monkeypatch.setattr(sg, "_auto_rank_strategy",
                        lambda m, n, platform: "counting")
    dst, payload, ok = _case(517, 29, 3, rng)
    mtype = jnp.asarray(rng.integers(1, 5, size=517).astype(np.int32))
    _assert_families_identical("counting slots", dst, mtype, payload, ok,
                               29, 2, need_max=True, spill_cap=8)
    # all-invalid through the full delivery with counting ranks
    dead = jnp.asarray(np.zeros(517, bool))
    _assert_families_identical("counting slots all-invalid", dst, mtype,
                               payload, dead, 29, 2)


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
def test_unknown_delivery_backend_raises(where, rng):
    """A typo must not silently fall back to `auto`: the name is checked
    where it is resolved (deliver_slots, the exchange) and where a system
    takes it, reduce-mode systems included."""
    from akka_tpu.batched import Emit, behavior
    from akka_tpu.batched.core import BatchedSystem

    if where == "per-call":
        dst, payload, ok = _case(33, 4, 2, rng)
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
