"""The wide merge delivery reads its markers out by position, not by a
second sort (ops/segment.py `_merged_segments` / `_compact_markers`).

Two contracts, both exact:

- the compaction helper moves every row with a displacement left by that
  displacement, in order, with no collision — checked against numpy
  boolean-mask selection on merged marker layouts, and against a numpy
  model of the same log-step routing that counts collisions;
- `_deliver_merge_wide` is bit-identical (raw bits, not `allclose`) to the
  two-sort kernel it replaced, a frozen copy of which lives here as the
  oracle and nowhere in `akka_tpu/`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_tpu.ops import segment as sg
from test_delivery_parity import REDUCE_SHAPES


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


def _messages(m, n, p, dtype, seed):
    rng = np.random.default_rng(seed)
    dst = jnp.asarray(rng.integers(-2, n + 2, size=m).astype(np.int32))
    ok = jnp.asarray(rng.random(m) > 0.15)
    if dtype == "i32":
        payload = jnp.asarray(rng.integers(-50, 50, (m, p)).astype(np.int32))
    else:  # non-integer floats: the sums do not add exactly
        payload = jnp.asarray(rng.standard_normal((m, p)).astype(np.float32))
        if dtype == "bf16":
            payload = payload.astype(jnp.bfloat16)
    return dst, payload, ok


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
    return tag.astype(bool), disp


def _route_numpy(flag, disp, max_disp):
    """The same log-step routing in plain numpy, counting collisions: a
    mover that lands on a row that is live and stays."""
    total = len(disp)
    live = flag.copy()
    ident = np.where(flag, np.arange(total), -1)
    d = disp.copy()
    collisions = 0
    for b in range(min(max_disp, total - 1).bit_length()):
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
    }
    for i in range(12):
        m, n = int(rng.integers(1, 700)), int(rng.integers(1, 300))
        hot = rng.integers(0, n + 1, size=max(1, n // 7))
        key = np.where(rng.random(m) < 0.5, rng.choice(hot, m),
                       rng.integers(0, n + 1, m))
        out[f"random-{i}-m{m}-n{n}"] = (key, n)
    return out


LAYOUTS = _layouts()


@pytest.mark.parametrize("name", list(LAYOUTS))
def test_compaction_equals_boolean_mask_selection(name):
    key, n = LAYOUTS[name]
    m = len(key)
    flag, disp = _merged_layout(key, n)
    rng = np.random.default_rng(m * 1000 + n)
    vals = (rng.standard_normal(m + n + 1).astype(np.float32),
            rng.integers(-9, 9, m + n + 1).astype(np.int32))
    cols, moved = jax.jit(sg._compact_markers, static_argnums=2)(
        tuple(jnp.asarray(v) for v in vals), jnp.asarray(disp), m)
    for v, c in zip(vals, cols):
        assert np.array_equal(_bits(np.asarray(c)[:n + 1]), _bits(v[flag]))
    # a moved row still carries its own displacement: position is count
    assert np.array_equal(np.asarray(moved)[:n + 1], disp[flag])
    assert int(disp.max()) == int((key <= n).sum()) == m  # the last marker
    ident, collisions = _route_numpy(flag, disp, m)
    assert collisions == 0
    assert np.array_equal(ident[:n + 1], np.nonzero(flag)[0])


def test_routing_model_never_collides_on_300_random_layouts():
    # the argument of `_compact_markers`'s docstring, tried: plain numpy,
    # so 300 layouts cost nothing to compile
    rng = np.random.default_rng(300)
    for _ in range(300):
        m, n = int(rng.integers(0, 400)), int(rng.integers(0, 200))
        key = rng.integers(0, n + 1, m) // int(rng.integers(1, 4))
        flag, disp = _merged_layout(key, n)
        ident, collisions = _route_numpy(flag, disp, m)
        assert collisions == 0
        assert np.array_equal(ident[:n + 1], np.nonzero(flag)[0])


@pytest.mark.parametrize("m,n", [(0, 4), (1, 1), (64, 5), (300, 1)])
def test_wide_merge_counts_come_from_marker_positions(m, n):
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
