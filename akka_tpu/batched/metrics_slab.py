"""In-graph metric slab: fixed-bucket int32 histograms riding the step carry.

The device side of the telemetry plane (ISSUE 7). Four distributions the
attention word's totals cannot express — mailbox occupancy at step entry,
message sojourn age in steps, supervision retry depth, ask promise-latch
latency in steps — are accumulated inside the jitted step as an
[N_HIST, N_BUCKETS] int32 slab living in the scan carry next to the
supervision counters (supervision.py N_COUNTERS pattern). Sharded runtimes
carry one slab row per shard ([n_shards, N_HIST, N_BUCKETS]) and the host
sums rows on drain, exactly like sup_counts.

Bucketing is integer-exact so the host-side numpy oracle (the *_np twins
below, mirroring testkit/chaos.py's jnp/numpy twin discipline) reproduces
every lane bit-for-bit: bucket(v) = #{b in BOUNDARIES : v >= b} with
power-of-two boundaries 2^0..2^(N_BUCKETS-2). A value v <= 0 lands in
bucket 0, v == 1 in bucket 1, [2^k, 2^(k+1)) in bucket k+1, and anything
>= 2^(N_BUCKETS-2) saturates into the last bucket. The compare-reduce form
(ops/segment.py counting_ranks' digit-histogram trick) needs no clz/log2
and vectorizes to one [m, N_BUCKETS-1] compare plus a row sum.

Accumulation is a masked segment_sum (the _deliver_scatter overflow-bucket
pattern, ops/segment.py): invalid rows route to a sacrificial bucket that
is sliced off, so they contribute exactly zero — the all-invalid edge is a
zero histogram, not a bucket-0 spike.

The slab is drained by the HOST only at the bridge pump's busy→idle edge
and the checkpoint barrier; a scalar "metrics epoch" (the slab's running
sum, a non-donated step output like the attention word) tells the host
whether a full slab fetch is worth the bytes. See docs/OBSERVABILITY.md.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# histogram lanes (rows of the slab)
(HIST_OCCUPANCY, HIST_SOJOURN, HIST_RETRY, HIST_ASK) = range(4)
N_HIST = 4
HIST_NAMES = ("mailbox_occupancy", "sojourn_steps", "retry_depth",
              "ask_latency_steps")

N_BUCKETS = 16
# power-of-two lower bounds: bucket(v) = sum(v >= BOUNDARIES)
BOUNDARIES = tuple(1 << k for k in range(N_BUCKETS - 1))  # 1, 2, 4, .. 2^14

# reserved state column: the bridge stamps the dispatched-step counter into
# a promise row's slot when ask() arms it; the step histograms
# (step - arm) when the reply latch flips (bridge.py ask / core._step_impl)
ASK_ARM_COL = "_m_ask_arm"
ASK_ARM_SPEC = ((), jnp.int32)


def bucket_of(v: jax.Array) -> jax.Array:
    """[m] int32 values -> [m] int32 bucket indices (traced in-graph)."""
    b = jnp.asarray(BOUNDARIES, jnp.int32)
    return jnp.sum((v[:, None] >= b[None, :]).astype(jnp.int32), axis=1)


def bucket_of_np(v: np.ndarray) -> np.ndarray:
    """Numpy twin of bucket_of — bit-identical by construction."""
    v = np.asarray(v, np.int64)
    b = np.asarray(BOUNDARIES, np.int64)
    return (v[:, None] >= b[None, :]).sum(axis=1).astype(np.int64)


def masked_hist(values: jax.Array, mask: jax.Array) -> jax.Array:
    """[N_BUCKETS] int32 histogram of values where mask holds. Invalid rows
    go to the sacrificial bucket N_BUCKETS (then sliced off) — the
    segment_sum overflow-bucket pattern of ops/segment.py."""
    safe = jnp.where(mask, bucket_of(values.astype(jnp.int32)), N_BUCKETS)
    return jax.ops.segment_sum(mask.astype(jnp.int32), safe,
                               num_segments=N_BUCKETS + 1)[:N_BUCKETS]


def masked_hist_np(values: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """Numpy oracle of masked_hist (int64 counts; compare with ==)."""
    mask = np.asarray(mask, bool)
    buckets = bucket_of_np(np.asarray(values))[mask]
    return np.bincount(buckets, minlength=N_BUCKETS).astype(np.int64)


def accumulate_step(metrics: jax.Array, old_state, new_state, old_alive,
                    delivered_count, inbox_valid, inbox_enq, step_count,
                    latch_col=None) -> jax.Array:
    """One step's histogram accumulation over an [N_HIST, N_BUCKETS] slab,
    traced inside the jitted step (single-device core and each shard of
    the shard_map body call this with their local blocks).

    The whole pass is cond-gated on the quiet predicate — any live inbox
    row, any retry-depth bump, any fresh ask-latch flip — so an idle step
    pays a few reductions, not four histogram scatters (the supervision
    apply_supervision gating pattern; a CPU-era budget of ≤1% of step
    time, not measured on the chip). A consequence worth knowing when reading
    the data: occupancy is sampled only on non-quiet steps, which is what
    keeps millions of idle-step zero samples from drowning bucket 0.

    Lanes:
      HIST_OCCUPANCY  per-lane delivered count at step entry, alive lanes
      HIST_SOJOURN    step_count - enqueue stamp of every live inbox row
                      (age in steps since last (re)stamp, at delivery)
      HIST_RETRY      new `_retries` depth of lanes whose counter grew
                      this step (zeros when supervision is compiled out)
      HIST_ASK        (step_count + 1) - ask-arm stamp of promise rows
                      whose latch flipped 0→1 this step (the +1: the latch
                      lands in the NEW carry, stamped by the host with the
                      dispatched-step counter — bridge.py ask())
    """
    i32 = jnp.int32
    zeros = jnp.zeros((N_BUCKETS,), i32)
    busy = jnp.any(inbox_valid)
    retry_mask = None
    if "_retries" in new_state:
        retry_mask = new_state["_retries"] > old_state["_retries"]
        busy = busy | jnp.any(retry_mask)
    newly = None
    if latch_col is not None and latch_col in new_state \
            and ASK_ARM_COL in old_state:
        newly = (new_state[latch_col] != 0) & (old_state[latch_col] == 0)
        busy = busy | jnp.any(newly)
    step = jnp.asarray(step_count, i32)
    age = jnp.maximum(step - inbox_enq, 0)

    def add(m):
        rows = [masked_hist(delivered_count.astype(i32), old_alive),
                masked_hist(age, inbox_valid)]
        rows.append(masked_hist(new_state["_retries"].astype(i32),
                                retry_mask)
                    if retry_mask is not None else zeros)
        if newly is not None:
            lat = jnp.maximum(step + 1 - old_state[ASK_ARM_COL], 0)
            rows.append(masked_hist(lat, newly))
        else:
            rows.append(zeros)
        return m + jnp.stack(rows)

    return jax.lax.cond(busy, add, lambda m: m, metrics)


def empty_slab(n_shards: int = 0) -> jax.Array:
    """Zero slab: [N_HIST, N_BUCKETS] (single device) or
    [n_shards, N_HIST, N_BUCKETS] (one row per shard)."""
    shape = (N_HIST, N_BUCKETS) if n_shards == 0 else \
        (n_shards, N_HIST, N_BUCKETS)
    return jnp.zeros(shape, jnp.int32)


def slab_totals(slab) -> np.ndarray:
    """Host side: collapse a (possibly per-shard) slab to one
    [N_HIST, N_BUCKETS] int64 total."""
    a = np.asarray(jax.device_get(slab), np.int64)
    return a.reshape((-1, N_HIST, N_BUCKETS)).sum(axis=0)


def slab_dict(slab) -> Dict[str, np.ndarray]:
    """Host side: named histogram lanes (HIST_NAMES -> [N_BUCKETS] int64)."""
    totals = slab_totals(slab)
    return {name: totals[i] for i, name in enumerate(HIST_NAMES)}


def bucket_label(i: int) -> str:
    """Human-readable bucket range, e.g. '0', '1', '4-7', '>=16384'."""
    if i == 0:
        return "0"
    lo = BOUNDARIES[i - 1]
    if i == N_BUCKETS - 1:
        return f">={lo}"
    hi = BOUNDARIES[i] - 1
    return str(lo) if hi == lo else f"{lo}-{hi}"


def bucket_upper_bounds() -> tuple:
    """Inclusive upper bounds per bucket for Prometheus-style `le` labels
    (the last bucket is unbounded -> +Inf)."""
    return tuple(b - 1 for b in BOUNDARIES) + (float("inf"),)


def bucket_percentile(lane: np.ndarray, q: float) -> float:
    """Nearest-rank percentile over one [N_BUCKETS] histogram lane,
    reported as the bucket's inclusive upper bound (conservative: the true
    value is <= the returned bound). Empty lane -> 0. The autoscaler's
    occupancy signal (event/pressure.py) reads p90 of the
    mailbox-occupancy lane through this."""
    counts = np.asarray(lane, np.int64)
    total = int(counts.sum())
    if total == 0:
        return 0.0
    rank = max(1, int(np.ceil(q * total)))
    cum = np.cumsum(counts)
    i = int(np.searchsorted(cum, rank))
    ub = bucket_upper_bounds()[i]
    return float(ub) if np.isfinite(ub) else float(BOUNDARIES[-1])
