#!/usr/bin/env python
"""Attribute the dynamic-delivery step's cost to its phases (VERDICT r3 #3).

The dynamic step is ONE fused XLA program, so phases cannot be timed from
the host inside it; instead each phase is jitted standalone on the same
shapes the 1M-actor dynamic ring uses and timed with block_until_ready.
The sum of phases ~ the full step (fusion makes the whole slightly cheaper
than the parts — the residual is reported as "fusion/overhead").

Phases of the merge-mode dynamic step (ops/segment.py _deliver_merge +
batched/core.py _step_impl):
  behavior   vmapped behavior switch + emission assembly
  sort1      lax.sort of messages+markers on the packed key (P+1 operands)
  cumsum     P+1 inclusive prefix sums over the sorted columns
  sort2      tag-compaction lax.sort moving markers to the tail
  diffs      first-order differences at the marker rows
  writeback  dynamic_update_slice of emissions into the inbox

Usage: python tools/attrib_dynamic.py [--actors N] [--repeat K] [--json]
Writes a markdown table to stdout (or a JSON blob with --json).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from akka_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402


def timed(fn, *args, repeat=5):
    """Median wall time of fn(*args) after a warmup call; returns (s, out)."""
    out = jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2], out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--actors", type=int, default=1 << 16)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--payload-width", type=int, default=4)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    n = args.actors
    p = args.payload_width
    host_inbox = 8
    m = n + host_inbox  # out_degree 1 ring + host region
    n1 = n + 1
    total = m + n1
    rng = np.random.default_rng(0)

    dst = jnp.asarray((np.arange(m) + 1) % n, jnp.int32)
    payload = jnp.asarray(rng.standard_normal((m, p)), jnp.float32)
    valid = jnp.ones((m,), jnp.bool_).at[n:].set(False)

    rows = {}

    # --- full step via the real system (the ground truth) ---
    from akka_tpu.models.baseline_benches import build_ring, seed_ring_full
    s = build_ring(n, static=False)
    seed_ring_full(s)
    t0 = time.perf_counter()
    s.run(1)
    s.block_until_ready()
    compile_s = time.perf_counter() - t0
    ts = []
    for _ in range(args.repeat):
        t0 = time.perf_counter()
        s.run(1)
        s.block_until_ready()
        ts.append(time.perf_counter() - t0)
    full = sorted(ts)[len(ts) // 2]

    # --- delivery as one jitted call ---
    from akka_tpu.ops.segment import deliver

    deliver_merge = jax.jit(
        lambda d, pl, v: deliver(d, pl, v, n, mode="merge"))
    rows["deliver(merge)"], _ = timed(deliver_merge, dst, payload, valid,
                                      repeat=args.repeat)
    deliver_scatter = jax.jit(
        lambda d, pl, v: deliver(d, pl, v, n, mode="scatter"))
    rows["deliver(scatter)"], _ = timed(deliver_scatter, dst, payload, valid,
                                        repeat=args.repeat)
    deliver_sort = jax.jit(
        lambda d, pl, v: deliver(d, pl, v, n, mode="sort"))
    rows["deliver(sort)"], _ = timed(deliver_sort, dst, payload, valid,
                                     repeat=args.repeat)

    # --- merge-mode sub-phases on the same shapes ---
    ok = valid & (dst >= 0) & (dst < n)
    key = jnp.where(ok, dst, n).astype(jnp.int32)
    key2 = jnp.concatenate([key * 2, jnp.arange(n1, dtype=jnp.int32) * 2 + 1])
    zc = jnp.zeros((n1,), jnp.float32)
    cols = tuple(jnp.concatenate([jnp.where(ok, payload[:, i], 0), zc])
                 for i in range(p))
    cnt = jnp.concatenate([ok.astype(jnp.int32), jnp.zeros((n1,), jnp.int32)])

    sort1 = jax.jit(lambda k, c, ct: jax.lax.sort((k,) + c + (ct,),
                                                  num_keys=1))
    rows["  sort1 (messages+markers)"], s1 = timed(sort1, key2, cols, cnt,
                                                   repeat=args.repeat)
    scols, scnt = s1[1:-1], s1[-1]

    csum = jax.jit(lambda c, ct: (tuple(jnp.cumsum(x) for x in c),
                                  jnp.cumsum(ct)))
    rows["  cumsum (P+1 prefix sums)"], (csums, ccnt) = timed(
        csum, scols, scnt, repeat=args.repeat)

    def sort2_fn(k, c, ct):
        tag = k & 1
        key3 = tag * (n + 2) + (k >> 1)
        return jax.lax.sort((key3,) + c + (ct,), num_keys=1)

    sort2 = jax.jit(sort2_fn)
    rows["  sort2 (tag compaction)"], s2 = timed(sort2, s1[0], csums, ccnt,
                                                 repeat=args.repeat)

    def diffs_fn(s2v):
        def d(c):
            t = c[m:]
            return jnp.concatenate([t[:1], t[1:] - t[:-1]])[:n]
        return tuple(d(c) for c in s2v[1:])

    rows["  diffs (marker readback)"], _ = timed(jax.jit(diffs_fn), s2,
                                                 repeat=args.repeat)

    # --- behavior + writeback = full - delivery (bounded estimate) ---
    platform = jax.devices()[0].platform

    out = {
        "platform": platform,
        "actors": n,
        "full_step_ms": round(full * 1e3, 3),
        "compile_plus_first_step_s": round(compile_s, 1),
        "phases_ms": {k: round(v * 1e3, 3) for k, v in rows.items()},
        "behavior+writeback_ms (residual)": round(
            max(full - min(rows["deliver(merge)"], rows["deliver(scatter)"],
                           rows["deliver(sort)"]), 0.0) * 1e3, 3),
    }
    if args.json:
        print(json.dumps(out))
        return
    print(f"# dynamic-step attribution — {platform}, {n} actors\n")
    print(f"full step: {out['full_step_ms']} ms   "
          f"(compile+first step: {out['compile_plus_first_step_s']} s)\n")
    print("| phase | ms |")
    print("|---|---|")
    for k, v in out["phases_ms"].items():
        print(f"| {k} | {v} |")
    print(f"| behavior+writeback (residual) | "
          f"{out['behavior+writeback_ms (residual)']} |")


if __name__ == "__main__":
    main()
