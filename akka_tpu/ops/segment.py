"""Delivery primitives: segment reductions over recipient ids.

TPU-native replacement for the reference's MPSC mailbox queues
(AbstractNodeQueue.java; dispatch/Mailbox.scala:467-497): a step's messages
are SoA columns (dst, payload, valid) and "enqueue + dequeue" becomes one
segment reduction per step — sums/maxes/counts land in per-actor slots.

Reduce delivery (`deliver`) has two kernels and one decision
(docs/DELIVERY_KERNELS.md):

- "scatter": XLA scatter-add. The choice on a CPU and for small M.
- "merge": the wide merge. ONE stable multi-operand sort of the M
  messages on their recipient, every payload column riding. In sorted
  order a segment's last row carries, in each column's running prefix,
  the total up to its key, and its position is the count; those segment
  ends are compressed to the dense front by static shifts
  (`_compact_markers`), differenced there, and expanded to their
  actors' rows by the mirror routing (`_expand_rows`). No gather, no
  scatter — what a TPU serializes — no second sort, and no row on the
  sort network that is not a message. The choice on a TPU above
  `SCATTER_MAX_M`.

`mode="auto"` asks `choose_reduce_kernel`, at trace time.

Slots delivery (`deliver_slots`) and the mesh runtime's exchange
bucketing (`exchange_uses_ranked`) each come in two families, picked
through the one `delivery_backend` seam (`_backend_impl`):

- "ranked" (rank-then-scatter, backend "xla", `auto` on a CPU): ONE sort
  over a narrow int32 key operand (on CPU a single packed (key,
  arrival-block) operand, or no sort at all — see `stable_ranks`)
  computes per-recipient ranks/offsets; every slot index, spill position
  and aggregation offset is then closed-form, and payload rows move with
  one scatter/gather — payload columns never ride the sort network.
- "wide" (backend "reference", `auto` on a TPU): every payload column
  rides ONE multi-operand sort; slots, spill region and the consumed
  aggregation (`_sorted_segments`) are read off those sorted rows by
  shift-and-select routings: no scatter, no gather, no second sort.

Both return `(SlotDelivery, place, retain)` and answer to one literal
queue, field for field and bit for bit, in tests/test_slots_delivery.py;
forcing "reference" is how a CPU test runs the chip's side.

All functions are jit-safe, static-shape, and XLA-fusable. The drop bucket
(index n_actors) absorbs invalid/out-of-range messages so no dynamic filtering
is needed.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .prefix import prefix_count, prefix_sum


def _resolve_platform(x: jax.Array) -> str:
    """Platform the computation will actually RUN on, for auto-mode kernel
    choice: prefer the operand's committed device (arrays placed on TPU
    while the process default is cpu — the repo's own cpu-first forcing
    workflow — must still pick the TPU kernel); tracers carry no devices,
    so fall back to the default backend that jit will target."""
    try:
        devs = x.devices()
        if devs:
            return next(iter(devs)).platform
    except Exception:  # noqa: BLE001 — tracers/abstract values
        pass
    return jax.default_backend()


class Delivery(NamedTuple):
    sum: jax.Array     # [N, P]
    max: jax.Array     # [N, P]
    count: jax.Array   # [N] int32


# ---------------------------------------------------------------------------
# delivery_backend seam
#
# A backend names the kernel FAMILY of slots delivery and of the mesh
# runtime's exchange bucketing; it has no say in reduce delivery.
#
#   None/"auto" — by platform: ranked on a CPU, wide on a TPU
#   "xla"       — ranked: narrow key rank + one payload gather/scatter
#   "reference" — wide: every column rides one multi-operand sort
# ---------------------------------------------------------------------------

DELIVERY_BACKENDS = ("auto", "xla", "reference")


def check_delivery_backend(backend: str | None) -> None:
    """Raise ValueError unless `backend` is None or a known name."""
    if backend is not None and backend not in DELIVERY_BACKENDS:
        raise ValueError(f"unknown delivery backend {backend!r}; "
                         f"expected None or one of {DELIVERY_BACKENDS}")


def _backend_impl(backend: str | None, platform: str) -> str:
    """Resolve a backend name to a kernel family: 'ranked' or 'wide'."""
    check_delivery_backend(backend)
    if backend == "reference":
        return "wide"
    if backend == "xla":
        return "ranked"
    return "ranked" if platform == "cpu" else "wide"


# At or below this message count reduce delivery scatters: the merge's
# expand and diffs run over all N actor rows whatever M is.
SCATTER_MAX_M = 1024


REDUCE_MODES = ("auto", "scatter", "merge")


def choose_reduce_kernel(m: int, n_actors: int, p: int,
                         platform: str = "cpu") -> str:
    """The one decision of reduce delivery, for mode="auto": "scatter" or
    "merge" from (M, N, P, platform).

    - cpu: scatter. XLA's CPU scatter-add beat the sort-based kernels at
      every shape measured there (docs/DELIVERY_KERNELS.md).
    - M <= SCATTER_MAX_M: scatter — a few host rows into a large actor
      space would pay an N-shaped expand for an M-shaped problem.
    - otherwise (a TPU): merge. Sorts vectorize there where 1M-row
      gathers and unsorted scatters serialize; it is the kernel every
      cell of the benchmark runs, and a traced run's scope table
      (`akka.deliver.<block>`, docs/OBSERVABILITY.md section 6) gives
      each block's device time (PERF.md section 5).

    merge forms segment sums as differences of ONE running prefix over
    all messages, which is exact only while that prefix stays inside the
    payload dtype's integer range; the layers that carry ask reply ids in
    a payload column do not go through "auto" for that reason
    (batched/bridge.py ASK_DELIVERY).
    """
    del n_actors, p  # present in the signature for future crossovers
    if platform == "cpu" or m <= SCATTER_MAX_M:
        return "scatter"
    return "merge"


def deliver(dst: jax.Array, payload: jax.Array, valid: jax.Array,
            n_actors: int, need_max: bool = False,
            mode: str = "auto") -> Delivery:
    """Reduce messages into per-actor inbox slots.

    dst: [M] int32 recipient ids; payload: [M, P]; valid: [M] bool.
    Invalid or out-of-range messages fall into a drop bucket.

    Modes:
    - "scatter": XLA scatter-add (segment_sum); each segment accumulates
      alone, so integer-valued payloads stay exact per segment.
    - "merge": the wide merge (`_deliver_merge_wide`), on every platform.
    - "auto": `choose_reduce_kernel` over (M, N, P, platform), decided at
      trace time so it is free at runtime.

    Counts and maxes are equal under both; sums differ only by float
    association (tests/test_delivery_parity.py holds both to one oracle).
    """
    if mode not in REDUCE_MODES:
        raise ValueError(f"unknown delivery mode {mode!r}; "
                         f"expected one of {REDUCE_MODES}")
    if mode == "auto":
        mode = choose_reduce_kernel(dst.shape[0], n_actors,
                                    payload.shape[1], _resolve_platform(dst))
    if mode == "scatter":
        return _deliver_scatter(dst, payload, valid, n_actors, need_max)
    return _deliver_merge_wide(dst, payload, valid, n_actors, need_max)


# Within-block triangle size for the packed-sort rank strategy: the
# [M/B, B, B] equality triangle costs M*B vectorized ops, the int32
# packing needs (n_actors + 2) * ceil(M/B) < 2^31. B=32 keeps both sides
# comfortable up to ~1M actors.
_RANK_BLOCK = 32


RANK_STRATEGIES = ("auto", "counting", "packed", "sort2")

# Key domains this small rank in ONE counting pass (radix covers the
# whole alphabet), where counting beats the packed sort outright on the
# CPU grid (docs/DELIVERY_KERNELS.md) — the sharded exchange's shard-id
# case.
_COUNT_SMALL_DOMAIN = 64


def _auto_rank_strategy(m: int, n_keys: int, platform: str) -> str:
    """The measured strategy crossover (docs/DELIVERY_KERNELS.md grid):
    counting wins wherever the packed strategy's int32 packing overflows
    (1.5-3x over the sort2 fallback at 1M x 64k and 1M x 1M) and for tiny
    key domains where it needs a single compare-reduce pass; the packed
    sort keeps a modest edge on mid-scale legal shapes; accelerators
    keep the vectorizing two-operand sort."""
    if platform != "cpu":
        return "sort2"
    nb = -(-m // _RANK_BLOCK)
    if (n_keys + 2) * nb >= 2 ** 31:
        return "counting"
    if n_keys + 2 <= _COUNT_SMALL_DOMAIN:
        return "counting"
    return "packed"


def stable_ranks(key: jax.Array, n_keys: int,
                 platform: str | None = None,
                 strategy: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """The 'rank' phase of rank-then-scatter: for each row, the number of
    EARLIER rows with the same key (its stable arrival rank within the
    recipient), plus per-key counts. Returns (rank [M] int32,
    counts [n_keys + 1] int32); keys must lie in [0, n_keys].

    Everything downstream — slot indices, spill positions, the inverse
    sort permutation inv = offsets[key] + rank — is closed-form from
    these two arrays, so no payload column ever rides a sort network.

    Three strategies, chosen at trace time (`strategy="auto"` follows
    the measured crossover in `_auto_rank_strategy`; the explicit names
    exist for parity tests):

    - counting: no sort network AT ALL — `counting_ranks` buckets rows
      by (key-digit, arrival-block), ONE exclusive cumsum over the
      compare-reduce histogram gives every row its cross-block offset,
      and a [B, B] equality triangle gives the within-block stable
      rank. O(M * radix) compare/cumsum work per radix pass; large key
      domains decompose into LSD passes so there is no int32 packing
      limit. The CPU pick for tiny key domains (sharded exchange) and
      for every shape where packing would overflow — including
      1M x 1M, where it measures 1.5-2.7x the sort2 fallback
      (docs/DELIVERY_KERNELS.md has the grid).
    - packed (CPU pick for mid-scale key domains): pack
      (key, block-of-B arrival index) into ONE int32 and single-operand
      lax.sort it — measured 5.3x faster than the generic-comparator
      two-operand sort. Cross-block ranks come back via vectorized
      binary search on the sorted packs; within-block ranks via the
      same [B, B] equality triangle. Requires
      (n_keys + 2) * ceil(M/B) < 2^31; falls back to counting beyond.
    - sort2 (TPU/GPU): the two-operand (key, iota) sort +
      head-flag/cummax ranks (sorts vectorize on accelerators; the
      counting strategy's data-dependent scatters and the packed
      strategy's searchsorted binary search both serialize into
      dependent gathers).
    """
    m = key.shape[0]
    nb = -(-m // _RANK_BLOCK)
    if platform is None:
        platform = _resolve_platform(key)
    if strategy not in RANK_STRATEGIES:
        raise ValueError(f"unknown rank strategy {strategy!r}; "
                         f"expected one of {RANK_STRATEGIES}")
    if strategy == "auto":
        strategy = _auto_rank_strategy(m, n_keys, platform)
    if strategy == "packed" and (n_keys + 2) * nb >= 2 ** 31:
        strategy = "counting"  # int32 packing would overflow; counting
        #                        has no such precondition and measures
        #                        1.5-3x faster than the sort2 fallback here
    if strategy == "counting":
        return counting_ranks(key, n_keys)
    if strategy == "packed":
        kp, packed = _pack_keys(key, n_keys)
        psorted = jax.lax.sort(packed)
        rank, counts = _ranks_from_packed(psorted, packed, kp, n_keys)
        return rank[:m], counts
    iota = jnp.arange(m, dtype=jnp.int32)
    skey, sidx = jax.lax.sort((key, iota), num_keys=1)
    head = jnp.concatenate([jnp.ones((1,), jnp.bool_), skey[1:] != skey[:-1]])
    start = jax.lax.cummax(jnp.where(head, iota, -1))
    rank = jnp.zeros((m,), jnp.int32).at[sidx].set(iota - start)
    bounds = jnp.searchsorted(
        skey, jnp.arange(n_keys + 2, dtype=jnp.int32)).astype(jnp.int32)
    return rank, bounds[1:] - bounds[:-1]


def _pack_keys(key: jax.Array, n_keys: int):
    """Pack (key, arrival-block) into a single int32 sort operand; rows
    past M pad with key n_keys + 1 so they sort last and never perturb
    counts. Returns (padded keys [nb*B], packed operand [nb*B])."""
    m = key.shape[0]
    b = _RANK_BLOCK
    nb = -(-m // b)
    pad = nb * b - m
    kp = (key if pad == 0 else
          jnp.concatenate([key, jnp.full((pad,), n_keys + 1, jnp.int32)]))
    blk = jnp.arange(nb * b, dtype=jnp.int32) // b
    return kp, kp * nb + blk


def _ranks_from_packed(psorted, packed, kp, n_keys: int):
    """The rank phase proper: cross-block same-key counts via vectorized
    binary search on the sorted packs, within-block counts via a [B, B]
    equality triangle. Returns (rank [nb*B], counts [n_keys + 1])."""
    b = _RANK_BLOCK
    nb = packed.shape[0] // b
    kb = jnp.searchsorted(
        psorted,
        jnp.arange(n_keys + 2, dtype=jnp.int32) * nb).astype(jnp.int32)
    counts = kb[1:] - kb[:-1]                              # [n_keys + 1]
    before = (jnp.searchsorted(psorted, packed).astype(jnp.int32)
              - kb[kp])                # same-key rows in earlier blocks
    k2 = kp.reshape(nb, b)
    tri = jnp.tril(jnp.ones((b, b), jnp.bool_), k=-1)      # tri[i, j] = j < i
    within = jnp.sum((k2[:, :, None] == k2[:, None, :]) & tri[None],
                     axis=2, dtype=jnp.int32)
    return before + within.reshape(-1), counts


# Counting-pass tuning, from the measured per-op constants on XLA CPU
# (docs/DELIVERY_KERNELS.md): a fused broadcast-compare-reduce runs at
# ~0.2 ns/element while scatter costs ~85 ns/row and cumsum ~10 ns/bin
# (log-depth passes). So a pass NEVER scatters — the histogram is a
# compare-reduce against the digit alphabet — and the radix stays small
# (<= 2^_COUNT_MAX_RADIX_BITS) so both the [nb, radix] compare and the
# flat histogram cumsum stay cheap; what large radixes would save —
# passes — costs less than the giant histograms they need.
_COUNT_MAX_RADIX_BITS = 8
_COUNT_MAX_BINS = 1 << 22


def _counting_pass(digit: jax.Array, n_digits: int, nb: int,
                   b: int) -> jax.Array:
    """One stable counting pass: the destination position of every padded
    row when rows are ordered by `digit` (values in [0, n_digits)) with
    arrival order as the tiebreak. For a row in block `blk` with digit
    `d` the destination is

        (# rows with a smaller digit)             flat-cumsum, digit-major
      + (# same-digit rows in earlier blocks)     ... same cumsum
      + (# same-digit rows earlier in this block) [B, B] equality triangle

    — the "histogram -> exclusive cumsum -> arrival-block cumsum"
    decomposition with no sort network and no scatter: the [nb, n_digits]
    per-block histogram is a broadcast compare against the digit alphabet
    reduced over the block axis (XLA fuses it; ~0.2 ns/element vs ~85
    ns/row for a scatter-add histogram), and ONE flat exclusive cumsum
    over its digit-major transpose yields the first two terms in a
    single gather."""
    d2 = digit.reshape(nb, b)
    alphabet = jnp.arange(n_digits, dtype=jnp.int32)
    hist = jnp.sum(alphabet[None, :, None] == d2[:, None, :],
                   axis=2, dtype=jnp.int32)                # [nb, n_digits]
    flat = jnp.cumsum(hist.T.reshape(-1))                  # digit-major
    excl = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            flat[:-1].astype(jnp.int32)])
    blk = jnp.arange(nb * b, dtype=jnp.int32) // b
    base = excl[digit * nb + blk]
    tri = jnp.tril(jnp.ones((b, b), jnp.bool_), k=-1)      # tri[i, j] = j < i
    within = jnp.sum((d2[:, :, None] == d2[:, None, :]) & tri[None],
                     axis=2, dtype=jnp.int32)
    return base + within.reshape(-1)


def counting_ranks(key: jax.Array, n_keys: int,
                   max_bins: int = _COUNT_MAX_BINS
                   ) -> Tuple[jax.Array, jax.Array]:
    """`stable_ranks` by bucketed counting sort — the rank phase with NO
    sort network: O(M * radix) compare/cumsum work per pass instead of
    an O(M log M) sort. Returns (rank [M] int32, counts [n_keys + 1]
    int32); keys must lie in [0, n_keys].

    One `_counting_pass` orders rows stably by one base-`radix` digit of
    the key; LSD composition of `passes = ceil(log_radix(domain))`
    passes orders them by the full key. Small key domains (the sharded
    exchange's shard ids, small-N tests) take exactly one pass with the
    alphabet trimmed to the domain. Between passes the permutation is
    applied to the keys by one narrow int32 scatter (positions are a
    bijection) and pass permutations compose by gather
    (pos = step[pos]); those scatters are the dominant cost, so the
    radix is chosen as the SMALLEST power of two that still achieves
    the minimum pass count reachable under _COUNT_MAX_RADIX_BITS. Rows
    past M pad with key n_keys + 1 so they order strictly last and
    never perturb ranks or counts.

    Unlike the packed strategy there is no int32 packing precondition:
    every intermediate is a position (< padded M) or a histogram count
    (<= M), so any (M, n_keys) that fits in memory is exact.
    """
    m = key.shape[0]
    b = _RANK_BLOCK
    nb = -(-m // b)
    pad = nb * b - m
    kp = (key if pad == 0 else
          jnp.concatenate([key, jnp.full((pad,), n_keys + 1, jnp.int32)]))
    n_vals = n_keys + 2              # real keys + drop bucket + pad key
    bitlen = max((n_vals - 1).bit_length(), 1)
    passes = -(-bitlen // _COUNT_MAX_RADIX_BITS)
    r_bits = -(-bitlen // passes)    # smallest radix with that pass count
    while nb * (1 << r_bits) > max_bins and r_bits > 1:
        passes += 1
        r_bits = -(-bitlen // passes)
    radix = 1 << r_bits
    pos = None                       # pos[i]: destination of original row i
    kcur = kp                        # keys arranged in the current order
    for p in range(passes):
        if p + 1 < passes:
            digit = (kcur >> (p * r_bits)) & (radix - 1)
            nd = radix
        else:
            digit = kcur >> (p * r_bits)
            nd = -(-n_vals // (radix ** p))  # top-digit alphabet only
        step = _counting_pass(digit, nd, nb, b)
        pos = step if pos is None else step[pos]
        if p + 1 < passes:
            kcur = jnp.zeros_like(kcur).at[step].set(
                kcur, unique_indices=True, mode="promise_in_bounds")
    counts = jnp.zeros((n_vals,), jnp.int32).at[kp].add(
        1, mode="promise_in_bounds")[:n_keys + 1]
    excl = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(counts)[:-1]])
    return pos[:m] - excl[key], counts


def _sorted_layout_sums(s2o, incl, masked, n_actors: int) -> jax.Array:
    """Per-segment sums with the EXACT float association of the wide merge
    kernel: the same `prefix_sum` of each column over the M rows in (key,
    arrival) order — its levels depend on that length — read at each
    segment's last row, ``incl[k] - 1``, and differenced. `s2o` (sorted
    position -> original row) brings the rows into that order by gather;
    an empty segment reads its predecessor's prefix and differences to 0,
    as the wide kernel's cleared rows do."""
    rows = masked[s2o]
    csum = jnp.stack([prefix_sum(rows[:, i]) for i in range(rows.shape[1])],
                     axis=1)
    at_end = jnp.where((incl > 0)[:, None], csum[jnp.maximum(incl - 1, 0)], 0)
    return jnp.concatenate([at_end[:1], at_end[1:] - at_end[:-1]],
                           axis=0)[:n_actors].astype(masked.dtype)


def _route_passes(max_disp: int, total: int) -> int:
    """Passes a log-step routing needs: the bits of the largest
    displacement that `total` rows allow."""
    return max(min(max_disp, total - 1), 0).bit_length()


def _compact_markers(cols, disp, max_disp: int):
    """Order-preserving compaction by static shifts: every row with
    ``disp > 0`` moves left by exactly ``disp`` rows, taking its entry of
    each column of `cols` along; rows with ``disp == 0`` stay, and are
    overwritten when a mover lands on them. Returns (cols, disp) in the
    new order, where a moved row still carries its own ``disp``.

    Precondition (what the segment ends of a sorted key column give): over
    the rows that matter — movers and the stayers that must survive —
    positions rise strictly and ``disp`` never falls, and every other row
    has ``disp == 0``. Then the log-step compress routes them with no
    collision, least significant bit first: in pass b every row whose
    ``disp`` has bit b set moves left by 2^b. After the bits below b a
    surviving row k sits at ``final_k + (disp_k >> b << b)``, which still
    rises strictly in k, so no two meet. A pass is a static slice-and-pad
    plus selects — contiguous moves, no gather, no scatter, no sort.
    `max_disp` bounds ``disp`` and fixes the number of passes at trace
    time.
    """
    def left(x, s):
        return jnp.concatenate([x[s:], jnp.zeros((s,), x.dtype)])

    for b in range(_route_passes(max_disp, disp.shape[0])):
        s = 1 << b
        coming = left(disp, s)
        arrives = (coming & s) != 0
        cols = tuple(jnp.where(arrives, left(c, s), c) for c in cols)
        disp = jnp.where(arrives, coming, jnp.where((disp & s) != 0, 0, disp))
    return cols, disp


def _expand_rows(cols, disp, max_disp: int):
    """The mirror of `_compact_markers`: every row with ``disp > 0`` moves
    RIGHT by exactly ``disp`` rows with its entry of each column, and a
    row it leaves reads 0 in every column afterwards (so a row nothing
    lands on is an empty actor's: 0 sums, 0 count, 0 max).

    Precondition (what dense rows j headed for actor rows k_j give): the
    live rows stand at 0, 1, 2, … with ``disp`` never falling, and every
    other row is 0 with ``disp == 0``. Most significant bit first this is
    the compress run backwards: after the bits down to b a live row j
    stands at ``j + (disp_j >> b << b)``, which rises strictly in j, so a
    mover never lands on a live row. Selects and static slices and pads
    only.
    """
    def right(x, s):
        return jnp.concatenate([jnp.zeros((s,), x.dtype), x[:-s]])

    for b in reversed(range(_route_passes(max_disp, disp.shape[0]))):
        s = 1 << b
        coming = right(disp, s)
        arrives = (coming & s) != 0
        leaves = (disp & s) != 0
        cols = tuple(jnp.where(arrives, right(c, s), jnp.where(leaves, 0, c))
                     for c in cols)
        disp = jnp.where(arrives, coming, jnp.where(leaves, 0, disp))
    return cols


def _sorted_segments(skey, scols, n_actors: int, p: int, need_max: bool):
    """Per-key sums of `scols` over rows already sorted by `skey` (int32
    in [0, n_actors] and rising, n_actors = the drop bucket, whose rows
    stand last), gather/scatter-free.

    In sorted order a non-empty segment is a contiguous run, and its last
    row (a "segment end": the next key differs) carries, in the inclusive
    prefix sum of each column (`ops/prefix.py`: dots where the dtype allows,
    not a `reduce-window`), the total of all rows keyed <= its own; its
    position + 1 is the count of those rows. The ends stand in key order,
    so two order-preserving routings by static shifts bring them home:
    `_compact_markers` compresses end j from its sorted position i_j to
    dense row j (i_j - j never falls: each end is at least one row after
    the last), per-segment sums and counts are first-order differences
    there, and `_expand_rows` moves dense row j to actor row k_j (k_j - j
    never falls: keys rise strictly over the ends), clearing what it
    vacates so that an empty actor reads 0. No index math touches a
    gather, nothing is sorted twice, and no row that is not a message
    ever rides the sort.

    Returns (sums: tuple of [n_actors] per column, rows_per_key
    [n_actors] int32, maxs: [n_actors, p] segment max over the first `p`
    columns with empty segments zeroed; all zeros unless `need_max`).
    """
    m = skey.shape[0]
    dense = min(m, n_actors)  # no more ends than rows, nor than actors
    with jax.named_scope("akka.deliver.prefix"):
        csums = tuple(prefix_sum(c) for c in scols)
        is_end = skey != jnp.concatenate(
            [skey[1:], jnp.full((min(m, 1),), n_actors, jnp.int32)])
        rank = prefix_count(is_end).astype(jnp.int32)  # ends at or before i
    acc = ()
    if need_max:
        with jax.named_scope("akka.deliver.max"):
            acc = _segmented_max_scan(skey, scols[:p])
    with jax.named_scope("akka.deliver.compact"):
        i = jnp.arange(m, dtype=jnp.int32)
        disp = jnp.where(is_end, i + 1 - rank, 0)  # i_j - j
        front, disp = _compact_markers(csums + acc + (skey,), disp, m)
        front = tuple(c[:dense] for c in front)
        disp = disp[:dense]
    with jax.named_scope("akka.deliver.diffs"):
        j = jnp.arange(dense, dtype=jnp.int32)
        live = j < rank[m - 1:]  # [1] against [dense]; no row if m == 0

        def diffs(c):
            d = jnp.concatenate([c[:1], c[1:] - c[:-1]])
            return jnp.where(live, d, 0)

        n_sums = len(csums)
        dsums = tuple(diffs(c) for c in front[:n_sums])
        dcount = diffs(j + disp + 1)  # i_j + 1: the rows keyed <= k_j
        dmaxs = tuple(jnp.where(live, c, 0) for c in front[n_sums:-1])
        out = jnp.where(live, front[-1] - j, 0)  # k_j - j
    with jax.named_scope("akka.deliver.expand"):
        def pad(c):
            return jnp.concatenate(
                [c, jnp.zeros((n_actors - dense,), c.dtype)])

        home = _expand_rows(tuple(pad(c) for c in dsums + (dcount,) + dmaxs),
                            pad(out), n_actors - 1)
    if need_max:
        maxs = jnp.stack(home[n_sums + 1:], axis=1)
    else:
        maxs = jnp.zeros((n_actors, p), scols[0].dtype)
    return home[:n_sums], home[n_sums], maxs


def _deliver_merge_wide(dst, payload, valid, n_actors: int,
                        need_max: bool) -> Delivery:
    """Gather/scatter-free segment reduction via ONE stable sort of the M
    messages on their recipient, every payload column riding (mode
    "merge", the TPU's `auto` choice above SCATTER_MAX_M); the segment
    ends of the sorted rows are read out and brought to their actors' rows
    by two shift-and-select routings (`_sorted_segments`). Blocks:
    `akka.deliver.merge_sort`, `.prefix`, `.compact`, `.diffs`, `.expand`
    (and `.max`)."""
    p = payload.shape[1]
    ok = valid & (dst >= 0) & (dst < n_actors)
    key = jnp.where(ok, dst, n_actors).astype(jnp.int32)
    cols = tuple(jnp.where(ok, payload[:, i], 0) for i in range(p))
    with jax.named_scope("akka.deliver.merge_sort"):
        s = jax.lax.sort((key,) + cols, num_keys=1)
    sums, counts, maxs = _sorted_segments(s[0], s[1:], n_actors, p, need_max)
    return Delivery(sum=jnp.stack(sums, axis=1).astype(payload.dtype),
                    max=maxs, count=counts)


def _segmented_max_scan(skey, scols):
    """Running max of each column within its run of equal `skey`: a
    log-step segmented max-scan (shift + select passes — contiguous
    moves, no gathers). At a segment end it is the segment's max."""
    total = skey.shape[0]
    acc = tuple(scols)
    shift = 1
    while shift < total:
        take = jnp.concatenate([jnp.zeros((shift,), jnp.bool_),
                                skey[:-shift] == skey[shift:]])
        acc = tuple(jnp.where(take, jnp.maximum(a, jnp.concatenate(
            [jnp.zeros((shift,), a.dtype), a[:-shift]])), a) for a in acc)
        shift *= 2
    return acc


def _deliver_scatter(dst, payload, valid, n_actors: int, need_max: bool) -> Delivery:
    ok = valid & (dst >= 0) & (dst < n_actors)
    safe_dst = jnp.where(ok, dst, n_actors)
    okf = ok[:, None]
    with jax.named_scope("akka.deliver.scatter"):
        sums = jax.ops.segment_sum(
            jnp.where(okf, payload, 0), safe_dst, num_segments=n_actors + 1)
        counts = jax.ops.segment_sum(
            ok.astype(jnp.int32), safe_dst, num_segments=n_actors + 1)
        counts = counts[:n_actors]
    if need_max:
        with jax.named_scope("akka.deliver.max"):
            neg_inf = _neg_inf(payload.dtype)
            maxs = jax.ops.segment_max(
                jnp.where(okf, payload, neg_inf), safe_dst,
                num_segments=n_actors + 1)
            maxs = jnp.where((counts > 0)[:, None], maxs[:n_actors], 0)
    else:
        maxs = jnp.zeros((n_actors, payload.shape[1]), payload.dtype)
    return Delivery(sum=sums[:n_actors], max=maxs, count=counts)


class SlotDelivery(NamedTuple):
    """Per-message mailbox delivery: each actor's first `slots` messages this
    step, in arrival order (per-sender FIFO), plus the EXACT commutative
    aggregation over all messages CONSUMED this step so reduce-kind behaviors
    coexisting in a slots-mode system lose nothing. With a spill region
    (spill_cap > 0), messages past the slot cap — and all mail addressed to
    suspended rows — are NOT consumed: they come back compacted in the spill_*
    outputs for redelivery next step (unbounded-mailbox semantics,
    dispatch/Mailbox.scala:647 UnboundedMailbox; suspension retention,
    actor/dungeon/FaultHandling.scala)."""

    valid: jax.Array    # [N, S] bool: the slots that hold a message
    count: jax.Array    # [N] int32 messages consumed this step
    sum: jax.Array      # [N, P] segment-sum over consumed messages (exact)
    max: jax.Array      # [N, P] segment-max over consumed (zeros unless
                        #        need_max)
    dropped: jax.Array  # [] int32 REAL losses this step (spill overflow, or
                        #    all overflow when spill_cap == 0)
    spill_dst: jax.Array      # [spill_cap] int32 LOCAL rows (-1 = empty)
    spill_type: jax.Array     # [spill_cap]
    spill_payload: jax.Array  # [spill_cap, P]
    spill_valid: jax.Array    # [spill_cap] bool


def deliver_slots(dst: jax.Array, mtype: jax.Array, payload: jax.Array,
                  valid: jax.Array, n_actors: int, slots: int,
                  need_max: bool = False, spill_cap: int = 0,
                  slots_kind=None, suspended=None,
                  backend: str | None = None, extent: int | None = None,
                  retain: bool = False):
    """Ordered per-message delivery into per-actor mailbox slots.

    The TPU-native form of the reference's discrete-envelope mailbox
    (dispatch/Mailbox.scala:260-277 processMailbox dequeues one Envelope at a
    time in FIFO order): a stable sort on recipient id — with arrival index as
    the implicit tiebreak — lines messages up in (recipient, seq) order, so a
    mailbox's messages are one contiguous run and its first `slots` are the
    run's first rows. The ranked family reads them out by closed-form gathers,
    the wide family by static shifts of the sorted rows whose run heads are
    then routed to their actors' rows (`_deliver_slots_wide`). Per-sender FIFO
    holds because a sender's emissions occupy increasing flat inbox indices and
    the sort is stable (SURVEY.md §7 hard parts: ordering under scatter
    delivery).

    dst: [M] int32; mtype: [M] int32; payload: [M, P]; valid: [M] bool.
    Arrival order IS the index order of the inputs.

    spill_cap == 0 (bounded mailbox): messages beyond `slots` for one actor
    are dropped and counted (dispatch/Mailbox.scala:415-443 — surface via
    dead letters host-side); slots_kind/suspended are ignored.

    spill_cap > 0 (unbounded semantics): overflow for slots-kind recipients
    (slots_kind: [N] bool — reduce-kind recipients always consume everything
    via the aggregation) and ALL mail to suspended rows (suspended: [N] bool)
    is excluded from slots AND from the aggregation, and returned compacted
    in (recipient, seq) order in the spill_* outputs; the caller writes it at
    the FRONT of the next step's inbox, so redelivered mail sorts before any
    fresh emission and per-sender FIFO is preserved across spill generations.
    Only spill-region overflow is a real (counted) drop.

    `backend` picks the kernel family (the `delivery_backend` seam, see
    the module docstring): rank-then-scatter ("xla"), the wide-sort
    kernel ("reference"), or by platform (None/"auto"). Results are
    bit-identical either way; an unknown name raises ValueError.

    Returns (delivery, place, retain), whatever the family and flags.
    `place(width)` gives the slots of the first `width` actor rows: their
    message types [width, S] int32 (an empty slot 0) and payloads [width,
    S, P]. `retain` is None unless asked for.

    `extent` (static int, under `n_actors`): the slots extent. The wide
    kernel's routing then fills the slots only when `place` asks, a
    [width*S] block for `width` the extent, and the N-wide block under
    `akka.deliver.place_all` for `n_actors`. Without one it builds the
    N-wide block with the delivery, and `place(n_actors)` hands it back.
    The ranked family fills every row anyway: its `place` slices. Every
    other output is as without an extent. None, or an extent of `n_actors`
    or more, is no extent.

    `retain=True` (a spill region required): the spill waits for the fold.
    The delivery comes without its spill (`spill_*` None, `dropped` 0), and
    `retain(taken)` -> (spill, dropped, retained), which, given the slots
    each actor's fold handled ([N] int32, at most `slots`), spills a
    slots-kind mailbox's messages from the first unhandled one on: the
    slots the fold left (`retained` counts those), then its overflow past
    `slots`, in order, ahead of the next step's mail (spill as the
    `spill_*` fields are). Without a fold that stops early (`taken` =
    `slots`) the spill is the one `retain=False` gives.
    """
    if retain and spill_cap <= 0:
        raise ValueError("retention spills: give a spill region")
    if extent is not None and extent >= n_actors:
        extent = None
    if _backend_impl(backend, _resolve_platform(dst)) == "ranked":
        return _deliver_slots_ranked(dst, mtype, payload, valid, n_actors,
                                     slots, need_max, spill_cap, slots_kind,
                                     suspended, retain)
    return _deliver_slots_wide(dst, mtype, payload, valid, n_actors, slots,
                               need_max, spill_cap, slots_kind, suspended,
                               extent, retain)


def _deliver_slots_ranked(dst, mtype, payload, valid, n_actors: int,
                          slots: int, need_max: bool, spill_cap: int,
                          slots_kind, suspended, retain: bool = False):
    """Rank-then-scatter slots delivery, entirely in the ORIGINAL row
    order: `stable_ranks` sorts narrow int32 keys only, and every slot
    index, spill position and aggregation offset is then closed-form
    from (rank, counts). One int32 scatter inverts the sort permutation;
    mailbox and spill rows are pure gathers off it, and the consumed
    aggregation pays one more narrow scatter — payload columns never
    ride a sort network. Its blocks carry the scopes `akka.deliver.rank`
    / `.place` / `.spill` / `.reduce`."""
    if payload.shape[0] == 0:
        # an inbox of no rows: one empty row (the drop bucket's) changes
        # nothing and gives the closed-form reads a row to stand on
        dst = jnp.full((1,), -1, dst.dtype)
        mtype = jnp.zeros((1,), mtype.dtype)
        valid = jnp.zeros((1,), valid.dtype)
        payload = jnp.zeros((1, payload.shape[1]), payload.dtype)
    m, p = payload.shape
    ok = valid & (dst >= 0) & (dst < n_actors)
    key = jnp.where(ok, dst, n_actors).astype(jnp.int32)
    cdst = jnp.clip(dst, 0, n_actors - 1)

    with jax.named_scope("akka.deliver.rank"):
        # --- key-sort + rank: arrival rank within recipient, per-key counts
        rank, counts_full = stable_ranks(key, n_actors, _resolve_platform(dst))
        counts = counts_full[:n_actors]

        incl = jnp.cumsum(counts_full)                          # [n+1]
        excl = jnp.concatenate([jnp.zeros((1,), jnp.int32), incl[:-1]])
        inv = excl[key] + rank

    if spill_cap > 0:
        susp_n = (suspended if suspended is not None
                  else jnp.zeros((n_actors,), jnp.bool_))
        kind_n = (slots_kind if slots_kind is not None
                  else jnp.ones((n_actors,), jnp.bool_))
        kind_m = (slots_kind[cdst] if slots_kind is not None
                  else jnp.ones((m,), jnp.bool_))
        susp_m = (suspended[cdst] if suspended is not None
                  else jnp.zeros((m,), jnp.bool_))
        spill = ok & (susp_m | (kind_m & (rank >= slots)))
        consumed = ok & ~spill
    else:
        spill = jnp.zeros((m,), jnp.bool_)
        consumed = ok

    with jax.named_scope("akka.deliver.place"):
        # --- place: ONE narrow int32 scatter inverts the sort permutation
        # (inv is a bijection on [0, M)); every mailbox row and spill row is
        # then a pure gather at a closed-form sorted position, so payload
        # columns are touched exactly once
        s2o = jnp.zeros((m,), jnp.int32).at[inv].set(
            jnp.arange(m, dtype=jnp.int32), unique_indices=True,
            mode="promise_in_bounds")
        kk = jnp.arange(n_actors * slots, dtype=jnp.int32) // slots
        jj = jnp.arange(n_actors * slots, dtype=jnp.int32) % slots
        buf_v = jj < counts[kk]
        if spill_cap > 0:
            buf_v &= ~susp_n[kk]
        row = s2o[jnp.minimum(excl[kk] + jj, m - 1)]
        buf_t = jnp.where(buf_v, mtype[row], 0)
        buf_p = jnp.where(buf_v[:, None], payload[row], 0)

    def spill_from(first):
        # spill compaction: the wide kernel assigns spill positions by a
        # cumsum over the (recipient, seq)-sorted spill flags; that same
        # position is closed-form here — per-key spill counts (suspended
        # rows spill everything, slots-kind rows spill from their `first`
        # unconsumed rank on: `slots`, or what the fold took) prefix-
        # summed across keys invert back to (key, within-rank) per spill
        # slot with one [spill_cap] binary search, no second scatter
        spc = jnp.where(susp_n, counts,
                        jnp.where(kind_n,
                                  jnp.maximum(counts - first, 0), 0))
        sp_excl = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(spc)])          # [n+1]
        ss = jnp.arange(spill_cap, dtype=jnp.int32)
        k_s = (jnp.searchsorted(sp_excl, ss, side="right").astype(jnp.int32)
               - 1)
        k_c = jnp.minimum(k_s, n_actors - 1)
        first_c = first if isinstance(first, int) else first[k_c]
        r_s = (ss - sp_excl[k_c]
               + jnp.where(susp_n[k_c], 0, first_c))
        srow = s2o[jnp.minimum(excl[k_c] + r_s, m - 1)]
        sp_v = ss < jnp.minimum(sp_excl[n_actors], spill_cap)
        sp_dst = jnp.where(sp_v, k_c, -1)
        sp_type = jnp.where(sp_v, mtype[srow], 0)
        sp_pl = jnp.where(sp_v[:, None], payload[srow], 0)
        dropped = jnp.maximum(sp_excl[n_actors] - spill_cap, 0)
        return spc, (sp_dst, sp_type, sp_pl, sp_v), dropped

    with jax.named_scope("akka.deliver.spill"):
        if retain:
            spc = jnp.where(susp_n, counts,
                            jnp.where(kind_n,
                                      jnp.maximum(counts - slots, 0), 0))
            dropped = jnp.asarray(0, jnp.int32)
            spill_out = (None,) * 4
        elif spill_cap > 0:
            spc, spill_out, dropped = spill_from(slots)
        else:
            spc = None
            in_cap = ok & (rank < slots)
            dropped = jnp.sum((ok & ~in_cap).astype(jnp.int32))
            spill_out = (jnp.full((0,), -1, jnp.int32),
                         jnp.zeros((0,), jnp.int32),
                         jnp.zeros((0, p), payload.dtype),
                         jnp.zeros((0,), jnp.bool_))

    with jax.named_scope("akka.deliver.reduce"):
        # --- reduce: exact consumed aggregation. _sorted_layout_sums
        # reproduces the wide kernel's prefix sums over the sorted rows bit
        # for bit (one gather through s2o instead of the wide sort); consumed
        # counts are integer-exact differences
        sums = _sorted_layout_sums(
            s2o, incl, jnp.where(consumed[:, None], payload, 0), n_actors)
        a_counts = counts - spc if spill_cap > 0 else counts
        if need_max:
            # non-consumed live rows contribute 0 exactly like the wide
            # kernel's masked columns; a segment with no rows at all
            # reads 0, as the wide kernel's cleared rows do
            vals = jnp.where(consumed[:, None], payload,
                             jnp.zeros((), payload.dtype))
            maxs = jax.ops.segment_max(vals, key,
                                       num_segments=n_actors + 1)[:n_actors]
            maxs = jnp.where((counts > 0)[:, None], maxs,
                             0).astype(payload.dtype)
        else:
            maxs = jnp.zeros((n_actors, p), payload.dtype)

    buf_t = buf_t.reshape(n_actors, slots)
    buf_p = buf_p.reshape(n_actors, slots, p)
    out = SlotDelivery(
        valid=buf_v.reshape(n_actors, slots), count=a_counts, sum=sums,
        max=maxs, dropped=dropped, spill_dst=spill_out[0],
        spill_type=spill_out[1], spill_payload=spill_out[2],
        spill_valid=spill_out[3])

    def place(width):
        return buf_t[:width], buf_p[:width]

    def retain_fn(taken):
        _, spill, lost = spill_from(taken)
        left = jnp.maximum(jnp.minimum(counts, slots) - taken, 0)
        retained = jnp.sum(jnp.where(kind_n & ~susp_n, left, 0))
        return spill, lost, retained.astype(jnp.int32)

    return out, place, retain_fn if retain else None


def _dense_rows(c, mine, length: int):
    """The dense front of `c` where `mine` ([dense] bool) holds, 0 on the
    rows behind it, `length` rows in all."""
    dense = mine.shape[0]
    return jnp.concatenate([jnp.where(mine, c[:dense], 0),
                            jnp.zeros((length - dense,), c.dtype)])


def _run_heads(skey, n_actors: int):
    """The first row of every run of the sorted keys, a head, and the two
    displacements that bring the heads home: head h stands at sorted
    position i_h and goes to dense row h, dense row h goes to its actor's
    row k_h; neither i_h - h nor k_h - h ever falls. Returns (head [M]
    bool, from_front [M]: i_h - h at dense row h, mine [dense] bool: the
    dense rows of live heads, not the drop bucket's, to_actor [N]: k_h - h
    at dense row h)."""
    m = skey.shape[0]
    dense = min(m, n_actors)
    iota = jnp.arange(m, dtype=jnp.int32)
    head = skey != jnp.concatenate(
        [jnp.full((min(m, 1),), -1, jnp.int32), skey[:-1]])
    upto = prefix_count(head).astype(jnp.int32)  # heads at or before i
    to_front = jnp.where(head, iota + 1 - upto, 0)
    # the key travels first, for the second displacement
    (hkey,), from_front = _compact_markers((skey,), to_front, m)
    j = jnp.arange(dense, dtype=jnp.int32)
    mine = j < jnp.sum((head & (skey < n_actors)).astype(jnp.int32))
    to_actor = _dense_rows(hkey[:dense] - j, mine, n_actors)
    return head, from_front, mine, to_actor


def _flags_to_heads(flags, from_front, mine, to_actor):
    """Each live head's recipient's entry of `flags` ([N] int32), at the
    head's sorted position, 0 on every other row: what ``flags[key]`` reads
    there, by three routings of one column and no gather. `to_actor` rides
    its own expand, so that actor row k_h holds k_h - h and a row without
    mail 0; with that displacement the flags of the actors that have mail
    compress to the dense front in key order (an actor that receives
    nothing stays where it is and is overwritten or masked by `mine`); and
    dense row h expands to the head's position i_h by `from_front`."""
    n_actors, m = flags.shape[0], from_front.shape[0]
    (back,) = _expand_rows((to_actor,), to_actor, n_actors - 1)
    (front,), _ = _compact_markers((flags,), back, n_actors - 1)
    return _expand_rows((_dense_rows(front, mine, m),), from_front, m)[0]


def _run_starts(head, hflags=None):
    """Every sorted row's run start (the position of its head) by one
    log-depth `lax.cummax` of (head ? position : -1): keys are monotone,
    so the latest head at or before a row is its own. With `hflags` (two
    bits a head, `_flags_to_heads`) the same scan carries them to the
    whole run beneath the position, which dominates, so it is still
    monotone. Returns (start, flags), flags None without `hflags`."""
    m = head.shape[0]
    iota = jnp.arange(m, dtype=jnp.int32)
    if hflags is None:
        return jax.lax.cummax(jnp.where(head, iota, -1)), None
    if m >= 1 << 29:
        raise ValueError(f"{m} inbox rows: position and flags share an int32")
    top = jax.lax.cummax(jnp.where(head, 4 * iota + hflags, -1))
    return top >> 2, top & 3


def _spill_rows(cols, spill_m, pos, iota, spill_cap: int, m: int):
    """The spill region: the sorted rows `spill_m` marks, in sorted order,
    moved to the front by one `_compact_markers` (`pos`: each one's place
    among them), `spill_cap` rows of key, type and payload columns; and
    what passed the region's end. Returns ((dst, type, payload, valid),
    dropped)."""
    front, _ = _compact_markers(cols, jnp.where(spill_m, iota - pos, 0), m)
    spilled = jnp.sum(spill_m.astype(jnp.int32))
    kept = jnp.arange(spill_cap, dtype=jnp.int32) < spilled

    def region(c, fill=0):
        c = jnp.concatenate(
            [c[:spill_cap],
             jnp.zeros((max(spill_cap - m, 0),), c.dtype)])
        return jnp.where(kept, c, fill)

    dropped = jnp.maximum(spilled - spill_cap, 0)
    return (region(front[0], -1), region(front[1]),
            jnp.stack([region(c) for c in front[2:]], axis=1), kept), dropped


def _deliver_slots_wide(dst, mtype, payload, valid, n_actors: int,
                        slots: int, need_max: bool, spill_cap: int,
                        slots_kind, suspended, extent=None,
                        retain: bool = False):
    """The wide-sort slots kernel ("reference" backend, `auto` on a
    TPU): every payload column rides the (P+3)-operand sort, after which
    a mailbox's messages are ONE contiguous run in arrival order. The
    enqueue reads that run where it lies, with no scatter and no gather:

    - slots (`akka.deliver.place`): a mailbox's r-th message is the row
      r past its run's first row, a head, so each row's rank is its
      distance from its head. A message the mailbox takes (live, the
      recipient not suspended, rank < S) belongs in slot key*S + rank of
      a row-major [N*S] block, and along the sorted rows that index
      rises strictly: the keys ascend, and within a run the rank rises
      by one. So the enqueue is one fixed permutation made of two
      monotone routings, as the segment ends of `_sorted_segments`
      travel: `_compact_markers` brings the taken rows (slot index, type,
      P payload values) to the dense front, and `_expand_rows` sends
      dense row j to its slot, a slot nobody wrote to reading 0. With a
      slots extent R < N the caller runs it (`place`) R rows wide, where
      only messages to actors below R are taken, or N wide (the block
      `akka.deliver.place_all`);
    - the recipients' flags (`akka.deliver.kind`; with a spill region and
      `slots_kind` or `suspended` given): two bits an actor travel from
      the actors' rows to the heads, the heads' keys compressed to the
      dense front giving the displacements (`_run_heads`,
      `_flags_to_heads`), and the scan that gives every row its run's
      start carries them down the run (`_run_starts`); they never ride
      the sort and nothing reads `slots_kind[dst]`;
    - spill (`akka.deliver.spill`): the spilled rows are a subset of the
      sorted rows in the order the spill region keeps, so one
      `_compact_markers` brings them to the front;
    - the aggregation reads the rows the sort left (`_sorted_segments`).
    """
    if n_actors * slots >= 1 << 31:
        raise ValueError(f"{n_actors} actors x {slots} slots: a slot index "
                         f"passes an int32")
    m, p = payload.shape
    ok = valid & (dst >= 0) & (dst < n_actors)
    key = jnp.where(ok, dst, n_actors).astype(jnp.int32)
    flagged = spill_cap > 0 and not (slots_kind is None and suspended is None)

    with jax.named_scope("akka.deliver.sort"):
        # ONE keyed sort carries every column: (recipient, arrival-index) as a
        # two-key sort IS the stable (recipient, seq) order, and payload/type
        # ride the sort network instead of being gathered afterwards (argsort +
        # x[order] is ~8x slower on TPU — gathers serialize, sorts vectorize)
        iota = jnp.arange(m, dtype=jnp.int32)
        fcols = tuple(payload[:, i] for i in range(p))
        s = jax.lax.sort((key, iota, mtype) + fcols, num_keys=2)
        skey, stype, scols = s[0], s[2], s[3:]

    with jax.named_scope("akka.deliver.place"):
        # the heads, in key order: head h goes from its sorted position to
        # dense row h, the dense rows past the last live head are cleared,
        # and dense row h goes to its actor's row k_h; `.kind` and
        # `retain_fn` route along these displacements
        head, from_front, mine, to_actor = _run_heads(skey, n_actors)

    hflags = None
    if flagged:
        with jax.named_scope("akka.deliver.kind"):
            kind_n = (slots_kind.astype(jnp.int32) if slots_kind is not None
                      else jnp.ones((n_actors,), jnp.int32))
            if suspended is not None:
                kind_n = kind_n + 2 * suspended.astype(jnp.int32)
            hflags = _flags_to_heads(kind_n, from_front, mine, to_actor)

    with jax.named_scope("akka.deliver.rank"):
        # rank within segment, gather-free: each message's distance from its
        # run's head; a flagged run reads its recipient's bits off the head
        start, sflags = _run_starts(head, hflags)
        rank = iota - start
        live = skey < n_actors
        if spill_cap > 0:
            # a flag not given: every recipient takes slots, none is suspended
            susp_s = (sflags >= 2 if suspended is not None
                      else jnp.zeros((m,), jnp.bool_))
            kind_s = ((sflags & 1) != 0 if slots_kind is not None
                      else jnp.ones((m,), jnp.bool_))
            spill_m = live & (susp_s | (kind_s & (rank >= slots)))
            takes = live & ~susp_s  # the mailbox takes its first `slots`
            consumed = live & ~spill_m
        else:
            spill_m = jnp.zeros((m,), jnp.bool_)
            takes = live
            consumed = live

    def place_slots(width):
        # the slots of the first `width` actor rows, a row-major [width*S]
        # block: a taken message goes to slot key*S + rank, which rises
        # strictly along the sorted rows, so one compress brings the taken
        # rows to the dense front in that order and one expand sends dense
        # row j to its slot; a slot nothing lands in reads 0
        rows = width * slots
        sel = takes & (rank < slots) & (skey < width)
        pos = prefix_count(sel).astype(jnp.int32) - 1
        front, _ = _compact_markers((skey * slots + rank, stype) + scols,
                                    jnp.where(sel, iota - pos, 0), m)
        dense = min(m, rows)  # no more taken rows than slots
        j = jnp.arange(dense, dtype=jnp.int32)
        mine = j < pos[m - 1:] + 1  # [1] against [dense]; no row if m == 0
        cols = _expand_rows(
            tuple(_dense_rows(c, mine, rows) for c in front[1:]),
            _dense_rows(front[0][:dense] - j, mine, rows), rows - 1)
        return (cols[0].reshape(width, slots),
                jnp.stack([c.reshape(width, slots) for c in cols[1:]],
                          axis=-1))

    if extent is None:
        with jax.named_scope("akka.deliver.place"):
            block = place_slots(n_actors)

    def place(width):
        if extent is None and width >= n_actors:
            return block
        with jax.named_scope("akka.deliver.place"):
            if width < n_actors:
                return place_slots(width)
            # a row at or past the extent takes slots this step
            with jax.named_scope("akka.deliver.place_all"):
                return place_slots(n_actors)

    with jax.named_scope("akka.deliver.spill"):
        # spill compaction: cumsum positions preserve the (recipient, seq) sort
        # order, so a spilled burst re-enters next step still in FIFO order;
        # spilled row number q moves left from its sorted position to row q
        if retain:
            dropped = jnp.asarray(0, jnp.int32)
            spill_out = (None,) * 4
        elif spill_cap > 0:
            pos = jnp.cumsum(spill_m.astype(jnp.int32)) - 1
            spill_out, dropped = _spill_rows((skey, stype) + scols, spill_m,
                                             pos, iota, spill_cap, m)
        else:
            dropped = jnp.sum((live & (rank >= slots)).astype(jnp.int32))
            spill_out = (jnp.full((0,), -1, jnp.int32), jnp.zeros((0,), jnp.int32),
                         jnp.zeros((0, p), payload.dtype), jnp.zeros((0,), jnp.bool_))

    with jax.named_scope("akka.deliver.reduce"):
        # exact consumed-message aggregation alongside the slots, via the same
        # segment-end kernel as _deliver_merge_wide (gather-free), on the
        # rows as the sort above left them; rows that are live but not
        # consumed keep their key, so the consumed count rides as a column
        # of its own
        (*sums, counts), arrived, maxs = _sorted_segments(
            skey, tuple(jnp.where(consumed, c, 0) for c in scols)
            + (consumed.astype(jnp.int32),), n_actors, p, need_max)
        sums = jnp.stack(sums, axis=1).astype(payload.dtype)

    with jax.named_scope("akka.deliver.place"):
        # a mailbox that takes any holds the first `slots` of what arrived
        buf_v = jnp.arange(slots, dtype=jnp.int32) < arrived[:, None]
        if spill_cap > 0 and suspended is not None:
            buf_v &= ~suspended.astype(jnp.bool_)[:, None]
    out = SlotDelivery(
        valid=buf_v, count=counts, sum=sums, max=maxs, dropped=dropped,
        spill_dst=spill_out[0], spill_type=spill_out[1],
        spill_payload=spill_out[2], spill_valid=spill_out[3])
    if not retain:
        return out, place, None
    kind_s = (sflags & 1) != 0 if slots_kind is not None \
        else jnp.ones((m,), jnp.bool_)
    susp_s = sflags >= 2 if suspended is not None \
        else jnp.zeros((m,), jnp.bool_)

    def retain_fn(taken):
        # what each fold took, from its actor's row to its mailbox's run:
        # the flags' trip to the heads, then down the run beside the
        # position, as `_run_starts` carries the flags
        bits = max(slots, 1).bit_length()
        if m >= 1 << (31 - bits):
            raise ValueError(f"{m} inbox rows: position and count share "
                             f"an int32")
        at_head = _flags_to_heads(taken, from_front, mine, to_actor)
        top = jax.lax.cummax(jnp.where(head, (iota << bits) + at_head, -1))
        first = top & ((1 << bits) - 1)
        over = live & ~susp_s & kind_s & (rank >= first)
        spill = over | (live & susp_s)
        retained = jnp.sum((over & (rank < slots)).astype(jnp.int32))
        pos = prefix_count(spill).astype(jnp.int32) - 1
        region, lost = _spill_rows((skey, stype) + scols, spill, pos, iota,
                                   spill_cap, m)
        return region, lost, retained

    return out, place, retain_fn


class StaticTopology:
    """Precompiled communication graph: delivery with NO runtime sort/scatter.

    When the actor graph is fixed (ring, trees, fan-in, router pools — the
    common case, and exactly what maps well to TPUs), the routing can be
    compiled at build time. `from_dst_table` pattern-matches the graph the way
    a communication compiler pattern-matches collectives:

    - "shift": dst[i] = (i+c) mod N  ->  delivery is jnp.roll (the on-chip
      analogue of lax.ppermute; ~memory-copy speed)
    - "mod":   dst[i] = i mod C      ->  reshape [G, C] + sum over G (the
      reduction-tree shape of a fan-in; full-bandwidth reduce)
    - "block": dst[i] = i // G       ->  reshape [C, G] + sum over G
    - "dense": uniform small fan-in  ->  gather inverse_edges [N, F], sum F
    - "csr":   anything else         ->  static sort permutation + cumsum
      differences at static segment boundaries

    Message VALUES and validity stay fully dynamic — only the wiring is static.
    Kind and scalar params are trace-time constants; only dense/csr carry
    device arrays (passed as runtime args so the HLO stays small).
    """

    def __init__(self, kind: str, n: int, k: int, shift: int = 0,
                 mod: int = 0, block: int = 0, inverse_edges=None,
                 perm=None, bounds=None):
        self.kind = kind
        self.n = n
        self.k = k
        self.shift = shift
        self.mod = mod
        self.block = block
        self.inverse_edges = inverse_edges
        self.perm = perm
        self.bounds = bounds

    def runtime_arrays(self) -> tuple:
        """Device arrays to pass through jit as arguments (pytree)."""
        if self.kind == "dense":
            return (self.inverse_edges,)
        if self.kind == "csr":
            return (self.perm, self.bounds)
        return ()

    @staticmethod
    def from_dst_table(dst_table, dense_max_fan_in: int = 4) -> "StaticTopology":
        """dst_table: [N, K] int — static destination of each actor's k-th
        out-slot; -1 = unused slot (runtime valid flags gate anyway).
        Host-side build (numpy)."""
        import numpy as np
        dt = np.asarray(dst_table, dtype=np.int64)
        n, k = dt.shape
        flat_dst = dt.reshape(-1)
        m = n * k
        slots = np.arange(m, dtype=np.int64)
        okm = flat_dst >= 0

        if k == 1 and okm.any():
            i_ok = slots[okm]
            d_ok = flat_dst[okm]
            # shift: dst = (i + c) mod n, all slots emitting
            if okm.all():
                c = int((d_ok[0] - i_ok[0]) % n)
                if ((i_ok + c) % n == d_ok).all():
                    return StaticTopology("shift", n, k, shift=c)
            # mod: dst = i mod C (C = number of distinct targets span)
            cands = np.unique(d_ok)
            c_mod = int(cands.max()) + 1
            if c_mod >= 1 and m % c_mod == 0 and (i_ok % c_mod == d_ok).all():
                return StaticTopology("mod", n, k, mod=c_mod)
            # block: dst = i // G
            if len(cands) > 0:
                g = m // (int(cands.max()) + 1)
                if g > 0 and m % g == 0 and (i_ok // g == d_ok).all():
                    return StaticTopology("block", n, k, block=g)

        order = np.argsort(flat_dst[okm], kind="stable")
        tgt = flat_dst[okm][order]
        src = slots[okm][order]
        counts = np.bincount(tgt, minlength=n) if tgt.size else np.zeros(n, np.int64)
        f = max(int(counts.max()) if counts.size else 1, 1)
        if f <= dense_max_fan_in:
            inv = np.full((n, f), -1, dtype=np.int32)
            starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
            pos = np.arange(tgt.shape[0]) - starts[tgt]
            inv[tgt, pos] = src.astype(np.int32)
            return StaticTopology("dense", n, k, inverse_edges=jnp.asarray(inv))
        perm = np.concatenate([src, slots[~okm]]).astype(np.int32)
        bounds = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
        return StaticTopology("csr", n, k, perm=jnp.asarray(perm),
                              bounds=jnp.asarray(bounds))


def deliver_static(topo: StaticTopology, arrays: tuple, payload: jax.Array,
                   valid: jax.Array, need_max: bool = False) -> Delivery:
    """Delivery over a static topology; `arrays` = topo.runtime_arrays()
    passed through jit (payload: [N*K, P] slot-indexed emissions)."""
    with jax.named_scope("akka.deliver.static"):
        p = payload.shape[1]
        n = topo.n

        if topo.kind == "shift":
            in_pl = jnp.roll(payload, topo.shift, axis=0)
            in_ok = jnp.roll(valid, topo.shift, axis=0)
            sums = jnp.where(in_ok[:, None], in_pl, 0)
            counts = in_ok.astype(jnp.int32)
            maxs = sums if need_max else jnp.zeros_like(sums)
            return Delivery(sum=sums, max=maxs, count=counts)

        if topo.kind in ("mod", "block"):
            if topo.kind == "mod":
                c = topo.mod
                g = payload.shape[0] // c
                pl3 = payload.reshape(g, c, p)          # sum over leading groups
                ok2 = valid.reshape(g, c)
                axis = 0
            else:
                g = topo.block
                c = payload.shape[0] // g
                pl3 = payload.reshape(c, g, p)
                ok2 = valid.reshape(c, g)
                axis = 1
            okf = jnp.expand_dims(ok2, -1)
            sums_c = jnp.sum(jnp.where(okf, pl3, 0), axis=axis)      # [C, P]
            counts_c = jnp.sum(ok2.astype(jnp.int32), axis=axis)     # [C]
            # targets are ids [0, C): place into the first C rows
            c_eff = min(c, n)
            sums = jnp.zeros((n, p), payload.dtype).at[:c_eff].set(sums_c[:c_eff])
            counts = jnp.zeros((n,), jnp.int32).at[:c_eff].set(counts_c[:c_eff])
            if need_max:
                neg_inf = _neg_inf(payload.dtype)
                maxs_c = jnp.max(jnp.where(okf, pl3, neg_inf), axis=axis)
                maxs = jnp.zeros((n, p), payload.dtype).at[:c_eff].set(
                    jnp.where((counts_c > 0)[:, None], maxs_c, 0)[:c_eff])
            else:
                maxs = jnp.zeros((n, p), payload.dtype)
            return Delivery(sum=sums, max=maxs, count=counts)

        if topo.kind == "dense":
            (inv,) = arrays                          # [N, F] small F
            safe = jnp.maximum(inv, 0)
            ok = (inv >= 0) & valid[safe]            # [N, F]
            gathered = payload[safe]                 # [N, F, P]
            okf = ok[..., None]
            sums = jnp.sum(jnp.where(okf, gathered, 0), axis=1)
            counts = jnp.sum(ok.astype(jnp.int32), axis=1)
            if need_max:
                neg_inf = _neg_inf(payload.dtype)
                maxs = jnp.max(jnp.where(okf, gathered, neg_inf), axis=1)
                maxs = jnp.where((counts > 0)[:, None], maxs, 0)
            else:
                maxs = jnp.zeros(sums.shape, payload.dtype)
            return Delivery(sum=sums, max=maxs, count=counts)

        # csr: static permutation + cumsum differences
        perm, bounds = arrays
        sp = payload[perm]                           # [M, P] dest-sorted (static)
        sv = valid[perm]
        sp = jnp.where(sv[:, None], sp, 0)
        csum = jnp.concatenate([jnp.zeros((1, p), sp.dtype),
                                jnp.cumsum(sp, axis=0)], axis=0)
        sums = csum[bounds[1:]] - csum[bounds[:-1]]
        cvalid = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(sv.astype(jnp.int32))])
        counts = cvalid[bounds[1:]] - cvalid[bounds[:-1]]
        if need_max:
            seg_ids = jnp.zeros((sp.shape[0],), jnp.int32).at[bounds[1:-1]].add(1)
            seg_ids = jnp.cumsum(seg_ids)
            neg_inf = _neg_inf(payload.dtype)
            maxs = jax.ops.segment_max(jnp.where(sv[:, None], sp, neg_inf), seg_ids,
                                       num_segments=n)
            maxs = jnp.where((counts > 0)[:, None], maxs, 0)
        else:
            maxs = jnp.zeros(sums.shape, payload.dtype)
        return Delivery(sum=sums, max=maxs, count=counts)


def _neg_inf(dtype):
    return jnp.asarray(-jnp.inf if jnp.issubdtype(dtype, jnp.floating)
                       else jnp.iinfo(dtype).min, dtype)


def exchange_uses_ranked(platform: str, backend: str | None = None) -> bool:
    """Kernel choice for sharded.py's exchange bucketing (filling the
    [D, C] all_to_all send buffers): same seam as the slots kernel —
    ranked on CPU, wide on TPU. Ranked: rank-in-group over the shard key,
    then a scatter from the original domain; the shard-id domain is tiny,
    so `stable_ranks` resolves to a single counting pass there (no sort
    at all). Wide: one stable sort on the shard key carries every column,
    after which each shard's rows are a contiguous run and its chunk of
    the buffer a masked copy of that run (`sharded._bucket_by_sort`: no
    scatter, which a TPU serializes row by row; `akka.exchange.bucket` in
    a traced run's scope table). Both fill bit-identical buffers."""
    return _backend_impl(backend, platform) == "ranked"


def route_one_hop(dst: jax.Array, perm_table: jax.Array) -> jax.Array:
    """Rewrite destinations through a routing table (router logics as index
    maps — SURVEY.md §2.11: RoundRobin = iota mod n, ConsistentHash = hash
    tensor)."""
    return perm_table[dst]


def compact_messages(dst: jax.Array, payload: jax.Array, valid: jax.Array,
                     capacity: int) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Stable-compact valid messages to the front of a fixed-size buffer.

    Returns (dst, payload, valid, dropped_count). Stable order preserves
    per-sender FIFO (SURVEY.md §7 hard parts: ordering under scatter delivery).
    """
    m = dst.shape[0]
    # positions of valid messages in stable order
    order = jnp.argsort(jnp.where(valid, 0, 1), stable=True)
    dst_s = dst[order]
    payload_s = payload[order]
    valid_s = valid[order]
    n_valid = jnp.sum(valid.astype(jnp.int32))
    if capacity >= m:
        pad = capacity - m
        return (jnp.pad(dst_s, (0, pad), constant_values=-1),
                jnp.pad(payload_s, ((0, pad), (0, 0))),
                jnp.pad(valid_s, (0, pad)),
                jnp.asarray(0, jnp.int32))
    dropped = jnp.maximum(n_valid - capacity, 0)
    return dst_s[:capacity], payload_s[:capacity], valid_s[:capacity], dropped
