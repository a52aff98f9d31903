"""Pallas ring-mailbox delivery prototype (SURVEY §2.10 native component).

The reference runtime gets per-sender FIFO for free: an MPSC linked queue
(AbstractNodeQueue.java) makes enqueue order THE mailbox order. Every XLA
kernel family in `segment.py` re-derives that order per step with a rank
pass (sort or counting) because XLA has no per-recipient mutable cursor.
Pallas does: a TPU grid executes sequentially, so a kernel that walks the
message stream in arrival-block order and bumps a per-recipient cursor in
on-chip memory IS the MPSC enqueue loop — recipient-id -> inbox-ring slot,
cursor bump, no global sort and no rank pass at all.

Two entry points, both registered behind the `delivery_backend` seam in
`segment.py` (backend="pallas" / deliver(mode="pallas")):

- `deliver_slots_ring`: the bounded mailbox (spill_cap == 0) semantics of
  `deliver_slots` — each recipient's first `slots` messages in arrival
  order land in its ring, later ones are counted as dropped, and the
  consumed aggregation accumulates in strict arrival order.
- `deliver_reduce`: the `Delivery` (sums/counts) reduction of `deliver`.

Validation and fallback matrix (docs/DELIVERY_KERNELS.md): on the CPU the
kernel runs in the Pallas interpreter, and `supported()` gates every call —
unsupported options (spill generations, slots_kind/suspended masks) fall
back to the ranked XLA kernels in the caller. On a TPU the kernel would be
compiled, and the compiler refuses it (`TPU_REFUSAL`): there `supported()`
is false and an explicit request for this backend raises in the caller; it
is a prototype whose scalar enqueue loop ROADMAP A4 is to vectorize.
Integer outputs (slots, types, valid, counts, dropped) are bit-identical
to the ranked/wide kernels; float sums accumulate in arrival order, which
the modes-agree oracle checks with allclose (association differs from the
cumsum-based kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from akka_tpu.ops.segment import Delivery, SlotDelivery, _neg_inf

# Arrival-block size: messages per grid step. The grid dimension is the
# arrival axis, and TPU grids execute sequentially, so cursor state in the
# revisited output blocks carries FIFO order across steps for free.
_BLOCK_M = 256

# Accumulator state (rings + cursors + sums) must fit on-chip when
# compiled for a real TPU core; interpret mode has no such limit but the
# same cap keeps pathological shapes off the scalar loop.
_STATE_BUDGET_BYTES = 1 << 23


# Mosaic's answer when this kernel is compiled on a TPU v5e at the parity
# test's shape (m=300, n=13, p=3, slots=2; jax 0.9.0, libtpu 0.0.34), for the
# reduce and the slots variant alike. The operation it names is the enqueue
# loop's one-element read at a dynamic index, `dst_ref[pl.ds(j, 1)]`.
TPU_REFUSAL = (
    "MosaicError: INTERNAL: Mosaic failed to compile TPU kernel: cannot "
    "statically prove that index in dimension 0 is a multiple of 256 "
    "(vector.load of memref<256xi32, vmem> -> vector<1xi32>)")


def _interpret() -> bool:
    """The Pallas interpreter is the CPU's way to run the kernel; every
    other platform compiles it."""
    return jax.default_backend() == "cpu"


def supported(n_actors: int, p: int, slots: int = 1, spill_cap: int = 0,
              slots_kind=None, suspended=None,
              platform: str | None = None) -> bool:
    """Static support matrix for the prototype. False on a TPU, where the
    compiler refuses the kernel (`TPU_REFUSAL`) and the caller raises; on
    the CPU callers fall back to the ranked kernels when False. Spill
    generations and per-recipient kind/suspension masks are redelivery
    machinery the ring kernel does not model (yet)."""
    if (platform or jax.default_backend()) == "tpu":
        return False
    if spill_cap > 0 or slots_kind is not None or suspended is not None:
        return False
    if n_actors < 1 or slots < 1 or p < 1:
        return False
    state = 4 * (n_actors * slots * (p + 2) + n_actors * (p + 1) + 1)
    return state <= _STATE_BUDGET_BYTES


def _ring_kernel(n_actors: int, slots: int, bm: int, with_slots: bool):
    """Kernel body: one arrival block per grid step, scalar enqueue loop.
    Output refs double as state — counts IS the per-recipient ring
    cursor, initialised on the first grid step and carried across steps
    because every step maps the same (whole-array) output block."""

    def kernel(dst_ref, t_ref, p_ref, v_ref, *out_refs):
        if with_slots:
            (buf_t_ref, buf_p_ref, buf_v_ref, counts_ref, sums_ref,
             drop_ref) = out_refs
        else:
            counts_ref, sums_ref, drop_ref = out_refs

        @pl.when(pl.program_id(0) == 0)
        def _init():  # first arrival block: empty mailboxes
            counts_ref[...] = jnp.zeros_like(counts_ref)
            sums_ref[...] = jnp.zeros_like(sums_ref)
            drop_ref[...] = jnp.zeros_like(drop_ref)
            if with_slots:
                buf_t_ref[...] = jnp.zeros_like(buf_t_ref)
                buf_p_ref[...] = jnp.zeros_like(buf_p_ref)
                buf_v_ref[...] = jnp.zeros_like(buf_v_ref)

        def enqueue(j, carry):
            d = dst_ref[pl.ds(j, 1)]                            # (1,)
            ok = (v_ref[pl.ds(j, 1)] != 0) & (d >= 0) & (d < n_actors)
            dc = jnp.clip(d[0], 0, n_actors - 1)
            cur = counts_ref[pl.ds(dc, 1)]                      # ring cursor
            counts_ref[pl.ds(dc, 1)] = cur + ok.astype(jnp.int32)
            pay = p_ref[pl.ds(j, 1), :]                         # (1, P)
            acc = sums_ref[pl.ds(dc, 1), :]
            sums_ref[pl.ds(dc, 1), :] = acc + jnp.where(ok[:, None], pay, 0)
            if with_slots:
                in_ring = ok & (cur < slots)
                slot = dc * slots + jnp.minimum(cur[0], slots - 1)
                buf_t_ref[pl.ds(slot, 1)] = jnp.where(
                    in_ring, t_ref[pl.ds(j, 1)], buf_t_ref[pl.ds(slot, 1)])
                buf_p_ref[pl.ds(slot, 1), :] = jnp.where(
                    in_ring[:, None], pay, buf_p_ref[pl.ds(slot, 1), :])
                buf_v_ref[pl.ds(slot, 1)] = jnp.where(
                    in_ring, 1, buf_v_ref[pl.ds(slot, 1)])
                drop_ref[...] = drop_ref[...] + jnp.sum(
                    (ok & (cur >= slots)).astype(jnp.int32))
            return carry

        jax.lax.fori_loop(0, bm, enqueue, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=("n_actors", "slots",
                                             "with_slots"))
def _run(dst, mtype, payload, valid, n_actors: int, slots: int,
         with_slots: bool):
    m, p = payload.shape
    bm = min(_BLOCK_M, max(m, 1))
    mp = -(-max(m, 1) // bm) * bm
    pad = mp - m
    if pad:
        dst = jnp.concatenate([dst, jnp.full((pad,), -1, jnp.int32)])
        mtype = jnp.concatenate([mtype, jnp.zeros((pad,), jnp.int32)])
        payload = jnp.concatenate(
            [payload, jnp.zeros((pad, p), payload.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.bool_)])
    row_spec = pl.BlockSpec((bm,), lambda i: (i,))
    pay_spec = pl.BlockSpec((bm, p), lambda i: (i, 0))
    out_shape = [
        jax.ShapeDtypeStruct((n_actors,), jnp.int32),           # counts
        jax.ShapeDtypeStruct((n_actors, p), payload.dtype),     # sums
        jax.ShapeDtypeStruct((1,), jnp.int32),                  # dropped
    ]
    out_specs = [
        pl.BlockSpec((n_actors,), lambda i: (0,)),
        pl.BlockSpec((n_actors, p), lambda i: (0, 0)),
        pl.BlockSpec((1,), lambda i: (0,)),
    ]
    if with_slots:
        out_shape = [
            jax.ShapeDtypeStruct((n_actors * slots,), jnp.int32),
            jax.ShapeDtypeStruct((n_actors * slots, p), payload.dtype),
            jax.ShapeDtypeStruct((n_actors * slots,), jnp.int32),
        ] + out_shape
        out_specs = [
            pl.BlockSpec((n_actors * slots,), lambda i: (0,)),
            pl.BlockSpec((n_actors * slots, p), lambda i: (0, 0)),
            pl.BlockSpec((n_actors * slots,), lambda i: (0,)),
        ] + out_specs
    return pl.pallas_call(
        _ring_kernel(n_actors, slots, bm, with_slots),
        grid=(mp // bm,),
        in_specs=[row_spec, row_spec, pay_spec, row_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interpret(),
    )(dst, mtype, payload, valid.astype(jnp.int32))


def _merge_style_max(dst, payload, ok, n_actors: int, p: int,
                     need_max: bool):
    """The wide merge kernel's max convention (exact, shared with the
    ranked family): invalid rows contribute -inf, recipients with no
    rows at all read back 0."""
    if not need_max:
        return jnp.zeros((n_actors, p), payload.dtype)
    neg_inf = _neg_inf(payload.dtype)
    key = jnp.where(ok, dst, n_actors).astype(jnp.int32)
    maxs = jax.ops.segment_max(jnp.where(ok[:, None], payload, neg_inf),
                               key, num_segments=n_actors + 1)[:n_actors]
    return jnp.where(maxs <= neg_inf, jnp.zeros_like(maxs),
                     maxs).astype(payload.dtype)


def deliver_reduce(dst, payload, valid, n_actors: int,
                   need_max: bool) -> Delivery:
    """`deliver` semantics via the ring kernel: sums/counts accumulate
    per recipient in strict arrival order (no sort, no rank pass)."""
    m, p = payload.shape
    mtype = jnp.zeros((m,), jnp.int32)
    counts, sums, _ = _run(dst, mtype, payload, valid, n_actors, 1, False)
    ok = valid & (dst >= 0) & (dst < n_actors)
    return Delivery(sum=sums,
                    max=_merge_style_max(dst, payload, ok, n_actors, p,
                                         need_max),
                    count=counts)


def deliver_slots_ring(dst, mtype, payload, valid, n_actors: int,
                       slots: int, need_max: bool) -> SlotDelivery:
    """Bounded-mailbox `deliver_slots` semantics (spill_cap == 0) via the
    ring kernel: first `slots` messages per recipient land in arrival
    order, the rest are counted as dropped, and the aggregation consumes
    every valid row — bit-identical integer fields vs the ranked/wide
    kernels, arrival-order float sums."""
    m, p = payload.shape
    buf_t, buf_p, buf_v, counts, sums, dropped = _run(
        dst, mtype, payload, valid, n_actors, slots, True)
    ok = valid & (dst >= 0) & (dst < n_actors)
    return SlotDelivery(
        types=buf_t.reshape(n_actors, slots),
        payload=buf_p.reshape(n_actors, slots, p),
        valid=buf_v.reshape(n_actors, slots).astype(jnp.bool_),
        count=counts,
        sum=sums,
        max=_merge_style_max(dst, payload, ok, n_actors, p, need_max),
        dropped=dropped[0],
        spill_dst=jnp.full((0,), -1, jnp.int32),
        spill_type=jnp.zeros((0,), jnp.int32),
        spill_payload=jnp.zeros((0, p), payload.dtype),
        spill_valid=jnp.zeros((0,), jnp.bool_),
    )
