"""The wide merge delivery sorts its M messages alone and reads the segment
ends out by position: compress, differences, expand (ops/segment.py
`_sorted_segments` / `_compact_markers` / `_expand_rows`).

Three contracts, all exact:

- the compaction helper moves every row with a displacement left by that
  displacement, in order, with no collision — checked against numpy
  boolean-mask selection on segment-end layouts (what the kernel gives
  it) and on merged marker layouts (what PR 27's kernel gave it), and
  against a numpy model of the same log-step routing that counts
  collisions;
- the expand helper is its mirror: every dense row moves right by its
  displacement and what it leaves reads 0 — checked against numpy fancy
  assignment and against the mirror model;
- `_deliver_merge_wide` is bit-identical (raw bits, not `allclose`) to the
  two-sort kernel it descends from, a frozen copy of which lives here as
  the oracle and nowhere in `akka_tpu/`, on integer-valued payloads
  inside each dtype's exact prefix range; on payloads that do not add
  exactly its sums are held to a float64 oracle within association
  error, counts and maxes exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_tpu.ops import segment as sg
from test_delivery_parity import REDUCE_SHAPES, _int_case, _segment_oracle


# ------------------------------------------------------ the frozen oracle
def _oracle_segmented_max_sorted(key_c, svals, tag, n_actors, dtype, m):
    total = key_c.shape[0]
    neg_inf = sg._neg_inf(dtype)
    vals = jnp.where((tag == 0)[:, None], svals, neg_inf)
    seg = key_c
    acc = vals
    shift = 1
    while shift < total:
        shifted = jnp.concatenate([jnp.full((shift, acc.shape[1]), neg_inf,
                                            acc.dtype), acc[:-shift]])
        sseg = jnp.concatenate([jnp.full((shift,), -1, seg.dtype),
                                seg[:-shift]])
        take = (sseg == seg)[:, None]
        acc = jnp.maximum(acc, jnp.where(take, shifted, neg_inf))
        shift *= 2
    key3 = tag * (n_actors + 2) + key_c
    cols = tuple(acc[:, i] for i in range(acc.shape[1]))
    s = jax.lax.sort((key3,) + cols, num_keys=1)
    mk = jnp.stack([c[m:] for c in s[1:]], axis=1)[:n_actors]
    return jnp.where(mk <= neg_inf, jnp.zeros_like(mk), mk).astype(dtype)


def _oracle_deliver_merge_two_sorts(dst, payload, valid, n_actors, need_max):
    """`_deliver_merge_wide` as it stood before the compaction (PR 26's
    tree, scopes left out): sort #1 with a `cnt` column riding, five
    cumsums, sort #2 on ``tag*(n+2) + key`` to bring the markers to the
    tail. Frozen: do not edit."""
    m, p = payload.shape
    n1 = n_actors + 1
    ok = valid & (dst >= 0) & (dst < n_actors)
    key = jnp.where(ok, dst, n_actors).astype(jnp.int32)
    key2 = jnp.concatenate([key * 2, jnp.arange(n1, dtype=jnp.int32) * 2 + 1])
    zcols = jnp.zeros((n1,), payload.dtype)
    cols = tuple(jnp.concatenate([jnp.where(ok, payload[:, i], 0), zcols])
                 for i in range(p))
    cnt = jnp.concatenate([ok.astype(jnp.int32), jnp.zeros((n1,), jnp.int32)])
    s1 = jax.lax.sort((key2,) + cols + (cnt,), num_keys=1)
    skey2, scols, scnt = s1[0], s1[1:-1], s1[-1]
    csums = tuple(jnp.cumsum(c) for c in scols)
    ccnt = jnp.cumsum(scnt)
    tag = skey2 & 1
    key_c = skey2 >> 1
    key3 = tag * (n_actors + 2) + key_c
    s2 = jax.lax.sort((key3,) + csums + (ccnt,), num_keys=1)
    mk = tuple(c[m:] for c in s2[1:-1])
    mc = s2[-1][m:]

    def diffs(c):
        return jnp.concatenate([c[:1], c[1:] - c[:-1]])[:n_actors]

    sums = jnp.stack([diffs(c) for c in mk], axis=1).astype(payload.dtype)
    counts = diffs(mc).astype(jnp.int32)
    if need_max:
        maxs = _oracle_segmented_max_sorted(
            key_c, jnp.stack(scols, axis=1), tag, n_actors, payload.dtype, m)
    else:
        maxs = jnp.zeros((n_actors, p), payload.dtype)
    return sg.Delivery(sum=sums, max=maxs, count=counts)


def _bits(x) -> np.ndarray:
    """The raw bits of an array, so that -0.0 != +0.0 and NaNs compare."""
    a = np.asarray(x)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.itemsize])


_DTYPES = {"f32": np.float32, "i32": np.int32, "bf16": jnp.bfloat16}


def _messages(m, n, p, dtype, seed):
    """`test_delivery_parity._int_case` from a seed of its own: strays,
    invalid rows, and integer-valued payloads inside the dtype's exact
    prefix range, so that every association of the prefix gives the same
    bits, whatever its length."""
    dst, _, ok, payload = _int_case(m, n, p, dtype=_DTYPES[dtype],
                                    rng=np.random.default_rng(seed))
    return jnp.asarray(dst), payload, jnp.asarray(ok)


@pytest.mark.parametrize("dtype", ["f32", "i32", "bf16"])
@pytest.mark.parametrize("need_max", [False, True])
@pytest.mark.parametrize("m,n,p", REDUCE_SHAPES)
def test_wide_merge_is_bit_identical_to_the_two_sort_oracle(m, n, p,
                                                            need_max, dtype):
    dst, payload, ok = _messages(m, n, p, dtype, seed=m * 31 + n)
    want = jax.jit(_oracle_deliver_merge_two_sorts, static_argnums=(3, 4))(
        dst, payload, ok, n, need_max)
    got = jax.jit(sg._deliver_merge_wide, static_argnums=(3, 4))(
        dst, payload, ok, n, need_max)
    for f in want._fields:
        x, y = getattr(want, f), getattr(got, f)
        assert x.dtype == y.dtype and x.shape == y.shape, f
        assert np.array_equal(_bits(x), _bits(y)), (
            f"{f}: first rows {np.asarray(x).ravel()[:6]} vs "
            f"{np.asarray(y).ravel()[:6]}")


@pytest.mark.parametrize("need_max", [False, True])
@pytest.mark.parametrize("m,n,p", REDUCE_SHAPES)
def test_wide_merge_float_sums_within_association_error(m, n, p, need_max):
    # payloads that do not add exactly: a sum is the difference of two
    # entries of one f32 prefix over the M sorted rows, and an entry of a
    # scan tree is log2(M) roundings of eps/2 deep, each on a partial sum
    # no larger than the column's total of |value|: the tolerance, set
    # from the dtype before any reading (readings: a twentieth of it).
    # Counts pass through no float; maxes through no arithmetic.
    rng = np.random.default_rng(m * 17 + n)
    dst = rng.integers(-2, n + 2, size=m).astype(np.int32)
    ok = rng.random(m) > 0.15
    vals = rng.standard_normal((m, p)).astype(np.float32)
    got = jax.jit(sg._deliver_merge_wide, static_argnums=(3, 4))(
        jnp.asarray(dst), jnp.asarray(vals), jnp.asarray(ok), n, need_max)
    sums, maxs, count = _segment_oracle(dst, vals, ok, n, need_max)
    live = ok & (dst >= 0) & (dst < n)
    tol = (int(np.ceil(np.log2(m))) * np.finfo(np.float32).eps
           * np.abs(vals[live]).sum(axis=0))
    err = np.abs(np.asarray(got.sum, np.float64) - sums)
    assert (err <= tol).all(), (err.max(axis=0), tol)
    assert np.array_equal(np.asarray(got.count), count)
    assert np.array_equal(np.asarray(got.max, np.float64), maxs)


# -------------------------------------------------- the compaction helper
def _merged_layout(key, n):
    """(disp, value) of the merged order of sort #1 for message keys `key`
    in [0, n]: marker k directly after every message keyed <= k."""
    m = len(key)
    key2 = np.concatenate([np.asarray(key, np.int64) * 2,
                           np.arange(n + 1) * 2 + 1])
    skey2 = np.sort(key2, kind="stable")
    tag = skey2 & 1
    pos = np.arange(m + n + 1)
    disp = np.where(tag == 1, pos - (skey2 >> 1), 0).astype(np.int32)
    assert int(disp.max()) == m  # the last marker: position is count
    return tag.astype(bool), disp


def _route_numpy(flag, disp, max_disp):
    """The same log-step routing in plain numpy, counting collisions: a
    mover that lands on a row that is live and stays."""
    total = len(disp)
    live = flag.copy()
    ident = np.where(flag, np.arange(total), -1)
    d = disp.copy()
    collisions = 0
    for b in range(max(min(max_disp, total - 1), 0).bit_length()):
        s = 1 << b
        leaves = live & ((d & s) != 0)
        src = np.nonzero(leaves)[0]
        stays = live & ~leaves
        collisions += int(stays[src - s].sum())
        moved_ident, moved_d = ident[src], d[src]
        live, ident, d = stays, np.where(stays, ident, -1), np.where(stays, d, 0)
        live[src - s] = True
        ident[src - s] = moved_ident
        d[src - s] = moved_d
    return ident, collisions


def _segment_ends(key, n):
    """What `_sorted_segments` routes, for message keys `key` in [0, n]
    (n = the drop bucket): (flag [M], disp [M]) of the compress — the
    last row of each run of one key < n, and how far left of it its dense
    row j lies — and (actor [J], out [J]) of the expand: dense row j's
    actor k_j and k_j - j."""
    skey = np.sort(np.asarray(key, np.int64), kind="stable")
    flag = skey != np.append(skey[1:], n)
    pos = np.nonzero(flag)[0]
    j = np.arange(len(pos))
    disp = np.zeros(len(skey), np.int32)
    disp[pos] = pos - j
    actor = skey[pos]
    assert len(actor) <= min(len(skey), n)
    # position is count: j + disp + 1 rows are keyed at or below k_j
    assert np.array_equal(j + disp[pos] + 1,
                          np.searchsorted(skey, actor, "right"))
    return flag, disp, actor, (actor - j).astype(np.int32)


# what `_compact_markers` has been given to route: PR 27's markers merged
# among the messages, and the segment ends of the messages alone
COMPRESS_LAYOUTS = {"markers": _merged_layout,
                    "segment-ends": lambda key, n: _segment_ends(key, n)[:2]}


def _route_numpy_right(out, n):
    """The expand's routing in plain numpy, most significant bit first,
    counting collisions: a mover that lands on a row that is live and
    stays. Dense row j starts at row j of n; returns where each ends."""
    live = np.zeros(n, bool)
    live[:len(out)] = True
    ident = np.where(live, np.arange(n), -1)
    d = np.zeros(n, np.int64)
    d[:len(out)] = out
    collisions = 0
    for b in reversed(range(max(n - 1, 0).bit_length())):
        s = 1 << b
        leaves = live & ((d & s) != 0)
        src = np.nonzero(leaves)[0]
        stays = live & ~leaves
        collisions += int(stays[src + s].sum())
        moved_ident, moved_d = ident[src], d[src]
        live, ident, d = stays, np.where(stays, ident, -1), np.where(stays, d, 0)
        live[src + s] = True
        ident[src + s] = moved_ident
        d[src + s] = moved_d
    return ident, collisions


def _layouts():
    rng = np.random.default_rng(27)
    out = {
        "no-messages": (np.zeros(0, np.int64), 9),
        "one-actor": (np.full(37, 3), 8),
        "all-invalid": (np.full(50, 6), 6),          # the drop bucket's key
        "not-pow2": (rng.integers(0, 14, 333), 13),
        "m-one": (np.array([0]), 1),
        "one-row": (np.zeros(0, np.int64), 0),       # L = 1: no pass at all
        "n-zero": (np.zeros(5, np.int64), 0),
        "all-before-marker-0": (np.zeros(64, np.int64), 5),  # disp = M
        "ring": (rng.permutation(256), 256),
        "m-less-than-n": (rng.integers(0, 301, 40), 300),
        "m-more-than-n": (rng.integers(0, 13, 500), 12),
        "keys-in-runs": (np.repeat(rng.permutation(40)[:25], 9), 40),
        "fan-in": (rng.integers(500, 510, 400), 512),  # few, far, long
    }
    for i in range(12):
        m, n = int(rng.integers(1, 700)), int(rng.integers(1, 300))
        hot = rng.integers(0, n + 1, size=max(1, n // 7))
        key = np.where(rng.random(m) < 0.5, rng.choice(hot, m),
                       rng.integers(0, n + 1, m))
        out[f"random-{i}-m{m}-n{n}"] = (key, n)
    return out


LAYOUTS = _layouts()


@pytest.mark.parametrize("layout", list(COMPRESS_LAYOUTS))
@pytest.mark.parametrize("name", list(LAYOUTS))
def test_compaction_equals_boolean_mask_selection(name, layout):
    key, n = LAYOUTS[name]
    m = len(key)
    flag, disp = COMPRESS_LAYOUTS[layout](key, n)
    kept = int(flag.sum())
    rng = np.random.default_rng(m * 1000 + n)
    # every row carries a value, as the cumsums do: the rows that are not
    # flagged are the garbage the routing has to write over
    vals = (rng.standard_normal(len(flag)).astype(np.float32),
            rng.integers(-9, 9, len(flag)).astype(np.int32))
    cols, moved = jax.jit(sg._compact_markers, static_argnums=2)(
        tuple(jnp.asarray(v) for v in vals), jnp.asarray(disp), m)
    for v, c in zip(vals, cols):
        assert np.array_equal(_bits(np.asarray(c)[:kept]), _bits(v[flag]))
    # a moved row still carries its own displacement: position is count
    assert np.array_equal(np.asarray(moved)[:kept], disp[flag])
    ident, collisions = _route_numpy(flag, disp, m)
    assert collisions == 0
    assert np.array_equal(ident[:kept], np.nonzero(flag)[0])


@pytest.mark.parametrize("layout", list(COMPRESS_LAYOUTS))
def test_routing_model_never_collides_on_300_random_layouts(layout):
    # the argument of `_compact_markers`'s docstring, tried: plain numpy,
    # so 300 layouts cost nothing to compile (strays and invalid rows as
    # the drop bucket's key n, M < N and M > N, hot keys)
    rng = np.random.default_rng(300)
    for _ in range(300):
        m, n = int(rng.integers(0, 400)), int(rng.integers(0, 200))
        key = rng.integers(0, n + 1, m) // int(rng.integers(1, 4))
        flag, disp = COMPRESS_LAYOUTS[layout](key, n)
        ident, collisions = _route_numpy(flag, disp, m)
        assert collisions == 0
        assert np.array_equal(ident[:flag.sum()], np.nonzero(flag)[0])


@pytest.mark.parametrize("name", list(LAYOUTS))
def test_expand_equals_fancy_assignment(name):
    key, n = LAYOUTS[name]
    _, _, actor, out = _segment_ends(key, n)
    ends = len(actor)
    rng = np.random.default_rng(len(key) * 1000 + n + 2)
    vals = (rng.standard_normal(n).astype(np.float32),
            rng.integers(1, 9, n).astype(np.int32))
    dense = tuple(np.where(np.arange(n) < ends, v, 0).astype(v.dtype)
                  for v in vals)
    disp = np.zeros(n, np.int32)
    disp[:ends] = out
    cols = jax.jit(sg._expand_rows, static_argnums=2)(
        tuple(jnp.asarray(v) for v in dense), jnp.asarray(disp), n - 1)
    for v, c in zip(dense, cols):
        want = np.zeros(n, v.dtype)
        want[actor] = v[:ends]
        assert np.array_equal(_bits(c), _bits(want))
    ident, collisions = _route_numpy_right(out, n)
    assert collisions == 0
    assert np.array_equal(ident[actor], np.arange(ends))
    assert (np.delete(ident, actor) == -1).all()


def test_expand_model_never_collides_on_300_random_layouts():
    rng = np.random.default_rng(301)
    for _ in range(300):
        m, n = int(rng.integers(0, 400)), int(rng.integers(0, 200))
        key = rng.integers(0, n + 1, m) // int(rng.integers(1, 4))
        _, _, actor, out = _segment_ends(key, n)
        ident, collisions = _route_numpy_right(out, n)
        assert collisions == 0
        assert np.array_equal(ident[actor], np.arange(len(actor)))


@pytest.mark.parametrize("m,n", [(0, 4), (1, 1), (64, 5), (300, 1)])
def test_wide_merge_counts_come_from_segment_end_positions(m, n):
    rng = np.random.default_rng(m + n)
    dst = rng.integers(-1, n + 1, size=m).astype(np.int32)
    ok = rng.random(m) > 0.2
    got = jax.jit(sg._deliver_merge_wide, static_argnums=(3, 4))(
        jnp.asarray(dst), jnp.ones((m, 2), jnp.float32), jnp.asarray(ok), n,
        False)
    live = ok & (dst >= 0) & (dst < n)
    want = np.bincount(dst[live], minlength=n)[:n]
    assert np.array_equal(np.asarray(got.count), want)
    assert np.array_equal(np.asarray(got.sum)[:, 0], want.astype(np.float32))
