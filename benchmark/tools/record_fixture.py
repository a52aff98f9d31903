#!/usr/bin/env python3
"""Record the small device trace that tests/benchmark reduce, and print what a
trace of this chip looks like (planes, lines, first events).

    chiprun -- python3 benchmark/tools/record_fixture.py

Writes chiprun_out/fixture/small.xplane.pb: three chunks of a 4,096-actor
dynamic ring under one TraceAnnotation each, python tracing off, so the file
stays small enough to commit."""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax

    from akka_tpu.models.baseline_benches import build_ring, seed_ring_full

    out = os.path.join(ROOT, "chiprun_out", "fixture")
    os.makedirs(out, exist_ok=True)
    d = jax.devices()[0]
    print("device", d.platform, d.device_kind, len(jax.devices()))
    print("memory_stats", d.memory_stats())
    s = build_ring(4096, static=False)
    seed_ring_full(s)
    s.run(4)
    s.block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tdir = os.path.join(out, "trace")
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir, profiler_options=opts)
    t0 = time.monotonic()
    for i in range(3):
        with jax.profiler.TraceAnnotation(f"bench.chunk.{i}"):
            s.run(4)
            s.block_until_ready()
        time.sleep(0.002)
    t1 = time.monotonic()
    jax.profiler.stop_trace()
    print("traced", t1 - t0, "s")
    path = sorted(glob.glob(os.path.join(tdir, "plugins/profile/*/*.xplane.pb")))[-1]
    dst = os.path.join(out, "small.xplane.pb")
    shutil.copy(path, dst)
    shutil.rmtree(tdir)
    print("fixture", dst, os.path.getsize(dst), "bytes")
    pd = jax.profiler.ProfileData.from_file(dst)
    for pl in pd.planes:
        print("PLANE", repr(pl.name))
        for ln in pl.lines:
            evs = list(ln.events)
            print("  LINE", repr(ln.name), len(evs))
            for e in evs[:6]:
                print("      ", repr(e.name), e.start_ns, e.duration_ns,
                      [(k, v) for k, v in e.stats][:8])
    return 0


if __name__ == "__main__":
    sys.exit(main())
