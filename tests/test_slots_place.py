"""The ordered-mailbox enqueue of the wide slots family as one routing
(ops/segment.py `_deliver_slots_wide`, `place`): every message a mailbox
takes goes to slot key*S + rank of a row-major [width*S] block by one
compress and one expand. Slot r of mailbox k holds the r-th message to k in
arrival order when k is below the width asked for and not suspended, and 0
everywhere else. Checked raw bit for bit against a plain queue in numpy:
more inbox rows than slots and fewer, S of 1, 4 and 16, an empty inbox, a
mailbox far over S, suspended recipients, keys past the width, and the width
of every actor row (the fallback and the kernel without an extent)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from akka_tpu.ops import segment as sg

WIDE = "reference"  # the wide slots family, a TPU's `auto`


def queue(dst, mtype, payload, valid, n, slots, width, suspended):
    """The literal mailbox: each actor's first `slots` messages in arrival
    order, for the actors below `width` that are not suspended."""
    types = np.zeros((width, slots), mtype.dtype)
    pl = np.zeros((width, slots, payload.shape[1]), payload.dtype)
    seen = np.zeros(n, np.int64)
    for i, k in enumerate(dst):
        if not valid[i] or not 0 <= k < n:
            continue
        r = seen[k]
        seen[k] += 1
        if k < width and r < slots and not (suspended is not None
                                            and suspended[k]):
            types[k, r] = mtype[i]
            pl[k, r] = payload[i]
    return types, pl


CASES = [  # n, m, slots, extent, spill_cap, dst, suspended, dtype
    pytest.param(300, 3000, 4, 96, 64, "random", "some", np.float32,
                 id="more-rows-than-slots"),
    pytest.param(300, 200, 16, 128, 64, "random", "some", np.float32,
                 id="fewer-rows-than-slots"),
    pytest.param(500, 700, 1, 128, 32, "random", None, np.float32,
                 id="one-slot"),
    pytest.param(64, 0, 4, 32, 16, "random", "some", np.float32,
                 id="empty-inbox"),
    pytest.param(200, 150, 16, 64, 256, "one", None, np.int32,
                 id="every-message-to-one-actor"),
    pytest.param(256, 1200, 4, 64, 512, "random", "half", np.float32,
                 id="suspended-recipients"),
    pytest.param(1000, 2500, 16, 64, 128, "past", None, np.int32,
                 id="keys-past-the-width"),
    pytest.param(300, 900, 4, 128, 0, "random", "some", np.float32,
                 id="no-spill-region"),
]


def inbox(n, m, dst, susp, dtype, extent):
    rng = np.random.default_rng([n, m, extent])
    where = {"random": lambda: rng.integers(-3, n + 3, m),
             "one": lambda: np.full(m, 7),
             "past": lambda: np.where(rng.random(m) < 0.3,
                                      rng.integers(0, extent, m),
                                      rng.integers(extent, n, m))}[dst]()
    share = {None: 0.0, "some": 0.1, "half": 0.5}[susp]
    return (where.astype(np.int32), rng.integers(1, 9, m).astype(np.int32),
            rng.integers(-99, 100, (m, 4)).astype(dtype),
            rng.random(m) < 0.9,
            None if susp is None else rng.random(n) < share,
            rng.random(n) < 0.7)


@pytest.mark.parametrize("every_row", [False, True])
@pytest.mark.parametrize("n,m,slots,extent,spill,dst,susp,dtype", CASES)
def test_place_is_the_queue(n, m, slots, extent, spill, dst, susp, dtype,
                            every_row):
    """`place(extent)` fills the slots of the rows below the extent,
    `place(n)` (the `place_all` block) those of every row."""
    d, t, p, v, suspended, kind = inbox(n, m, dst, susp, dtype, extent)
    width = n if every_row else extent

    @jax.jit
    def placed(d, t, p, v):
        out = sg.deliver_slots(
            d, t, p, v, n, slots, spill_cap=spill,
            slots_kind=jnp.asarray(kind),
            suspended=None if suspended is None else jnp.asarray(suspended),
            backend=WIDE, extent=extent)
        return out[-1](width)

    types, payload = placed(*map(jnp.asarray, (d, t, p, v)))
    want_t, want_p = queue(d, t, p, v, n, slots, width,
                           suspended if spill > 0 else None)
    assert types.shape == (width, slots) and types.dtype == want_t.dtype
    assert payload.shape == (width, slots, 4) and payload.dtype == dtype
    assert np.asarray(types).tobytes() == want_t.tobytes()
    assert np.asarray(payload).tobytes() == want_p.tobytes()
    if m:
        assert want_t.any()  # something was placed


@pytest.mark.parametrize("n,m,slots,extent,spill,dst,susp,dtype", CASES)
def test_the_kernel_without_an_extent_is_the_queue(n, m, slots, extent, spill,
                                                   dst, susp, dtype):
    """No extent: the delivery's own `types` and `payload` hold every
    row's slots, and `valid` marks the slots the queue filled."""
    d, t, p, v, suspended, kind = inbox(n, m, dst, susp, dtype, extent)
    out = sg.deliver_slots(
        *map(jnp.asarray, (d, t, p, v)), n, slots, spill_cap=spill,
        slots_kind=jnp.asarray(kind),
        suspended=None if suspended is None else jnp.asarray(suspended),
        backend=WIDE)
    want_t, want_p = queue(d, t, p, v, n, slots, n,
                           suspended if spill > 0 else None)
    assert np.asarray(out.types).tobytes() == want_t.tobytes()
    assert np.asarray(out.payload).tobytes() == want_p.tobytes()
    ok = v & (d >= 0) & (d < n)
    arrived = np.bincount(d[ok], minlength=n)
    want_v = np.arange(slots)[None, :] < arrived[:, None]
    if spill > 0 and suspended is not None:
        want_v &= ~suspended[:, None]
    assert (np.asarray(out.valid) == want_v).all()


def test_a_slot_index_that_passes_an_int32_is_refused():
    """key*S + rank is an int32: N*S of 2^31 or more raises before any
    work; just under it the kernel traces."""
    small = (jnp.zeros((8,), jnp.int32), jnp.zeros((8,), jnp.int32),
             jnp.zeros((8, 4), jnp.float32), jnp.ones((8,), jnp.bool_))
    with pytest.raises(ValueError, match="int32"):
        sg.deliver_slots(*small, 1 << 27, 16, spill_cap=8, backend=WIDE)
    with pytest.raises(ValueError, match="int32"):
        sg.deliver_slots(*small, 1 << 31, 1, backend=WIDE)
    types, payload = jax.eval_shape(
        lambda *a: sg.deliver_slots(*a, (1 << 27) - 1, 16, spill_cap=8,
                                    backend=WIDE, extent=1024)[-1](1024),
        *small)
    assert types.shape == (1024, 16) and payload.shape == (1024, 16, 4)
