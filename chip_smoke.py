#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the main path once, end to end, through the entry points a user calls
(the `models/baseline_benches` builders over `BatchedSystem` /
`ShardedBatchedSystem`, the `tpu-batched` dispatcher with `device_props`,
`DeviceShardRegion`, `RegionBackend`, `GatewayServer` / `GatewayClient`) at
the sizes of `BASELINE.json`'s configs, and checks every count exactly.

    python3 chip_smoke.py             one chip: phases a b c r s d, then the served
                                      path twice (serialized, continuous waves)
    python3 chip_smoke.py --chips 4   one process driving four chips: phase e
                                      (cross-shard ring) and the served path
    JAX_PLATFORMS=cpu python3 chip_smoke.py --tiny
                                      the same control flow at toy sizes, to
                                      debug on a CPU (tests run this)

Without --tiny it exits non-zero, and prints no result line, unless
`jax.devices()[0].platform == "tpu"`, every phase finished and every check
held. The last line of standard output is then one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.

Which process owns the chip. A chip belongs to one process at a time, so:
- This process (the parent) never imports jax. It starts the workers below
  ONE AFTER ANOTHER, each in its own session, waits for each to exit, and
  kills a worker's whole process group when it overruns its time limit.
- A worker (`--worker a,b,c,r,s,d`) is the one process that initialises the
  backend and holds the chip for all of its phases. The first worker runs
  the device plane; the second runs phase a again — its compile is warm only
  if the persistent compile cache hit across processes — and then serves.
- While serving, the worker starts the load generator (`--load`) as a child
  over a real socket with JAX_PLATFORMS=cpu set for it. The generator
  imports akka_tpu.gateway (which imports jax) but asserts at exit that it
  never initialised a backend.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

from akka_tpu.utils.compile_cache import compile_cache_dir

HERE = os.path.abspath(__file__)
TIME_LIMIT_S = 1200.0  # the contract's limit for the whole run

# sizes: BASELINE.json's configs, and a toy copy of each for --tiny
FULL = dict(
    n_actors=1 << 20, steps=64,                  # configs 2/3: 1M actors
    fan_collectors=1000,                         # config 3: 1M -> 1k
    pool_routees=100_000,                        # config 4: 100k routees
    bank=(1 << 17, 16, 1 << 14),                 # accounts, slots, spill rows
    tell_actors=1 << 19, tells=4096, tell_threads=8, tell_rounds=40,
    asks=8,
    shards=256, eps=4096,                        # config 5: 256 x 4k
    # 128 connections, one request in flight each: twice the ingest window
    # (64), so a full window is always waiting when a wave resolves
    conns=128, entities=10240, adds=3072, tenants=8)
TINY = dict(
    n_actors=1 << 10, steps=4, fan_collectors=16, pool_routees=100,
    bank=(1 << 9, 4, 1 << 10),
    tell_actors=256, tells=512, tell_threads=4, tell_rounds=34, asks=3,
    shards=8, eps=64, conns=8, entities=64, adds=96, tenants=4)


class SmokeFailure(AssertionError):
    """A check that is off by even one count."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


T_START = time.monotonic()


def note(msg: str) -> None:
    """Progress, on stderr: standard output carries results only."""
    print(f"[chip_smoke +{time.monotonic() - T_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


# =========================================================== load generator
def run_load(args: argparse.Namespace) -> int:
    """The load generator: its own process, a real socket, no device.

    `conns` connections (one thread and one GatewayClient each) first send
    `adds` add requests spread over `entities` distinct entities, every
    request through `request_retry` with sheds retried; after all adds were
    answered, every entity is read with `get` and compared with the dict
    oracle, and `sum` is read over the admin channel."""
    import random

    from akka_tpu.gateway import GatewayClient

    rng = random.Random(args.seed)
    names = [f"acct-{args.seed}-{i}" for i in range(args.entities)]
    # every add names its entity, tenant and integer value up front, so the
    # oracle is fixed before a byte is sent; a hot hundredth takes a third
    # of the adds, so same-entity asks meet in one wave and are deferred
    hot = names[: max(1, args.entities // 100)]
    plan = []
    for i in range(args.adds):
        ent = rng.choice(hot) if i % 3 == 0 else rng.choice(names)
        plan.append((f"tenant{i % args.tenants}", ent,
                     float(rng.randint(1, 5))))
    intended = dict.fromkeys(names, 0.0)
    for _t, ent, v in plan:
        intended[ent] += v
    base = {}
    if args.base:  # totals the previous leg left behind
        with open(args.base) as f:
            base = json.load(f)

    lock = threading.Lock()
    stats = {"ok": 0, "shed_retried": 0, "attempts": 0, "other": []}
    got = {}

    def worker(k: int, jobs, op: str) -> None:
        # the timeout outlasts a step program that compiles mid-load (the
        # first wave that defers a same-entity ask runs a new step count)
        client = GatewayClient("127.0.0.1", args.port, timeout=300.0)
        try:
            for tenant, ent, v in jobs[k::args.conns]:
                rep = client.request_retry(tenant, ent, op, v,
                                           deadline_s=args.deadline,
                                           retry_sheds=True)
                with lock:
                    stats["attempts"] += rep["attempts"]
                    if rep.get("status") == "ok":
                        stats["ok"] += 1
                        stats["shed_retried"] += rep["attempts"] > 1
                        if op == "get":
                            got[ent] = float(rep["value"])
                    else:
                        stats["other"].append(rep)
        finally:
            client.close()

    def fan_out(jobs, op: str) -> None:
        errors = []

        def guarded(k: int) -> None:
            try:
                worker(k, jobs, op)
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(repr(e))

        threads = [threading.Thread(target=guarded, args=(k,))
                   for k in range(args.conns)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise RuntimeError(f"load connection failed: {errors[:3]}")

    t0 = time.monotonic()
    fan_out(plan, "add")
    t_adds = time.monotonic() - t0
    note(f"load: {len(plan)} adds answered in {t_adds:.1f} s")
    # quiescence: every add was answered above; now read everything back
    fan_out([(f"tenant{i % args.tenants}", ent, 0.0)
             for i, ent in enumerate(names)], "get")
    t_all = time.monotonic() - t0
    note(f"load: {len(names)} gets answered, {t_all:.1f} s in all")
    admin = GatewayClient("127.0.0.1", args.port, timeout=120.0)
    try:
        final = admin.request_retry("__admin", "", "sum", deadline_s=120.0)
    finally:
        admin.close()

    want = {e: base.get(e, 0.0) + v for e, v in intended.items()}
    wrong = [(e, got.get(e), want[e]) for e in names if got.get(e) != want[e]]
    import jax._src.xla_bridge as xb  # imported by akka_tpu.gateway anyway
    result = {
        "requests": len(plan) + len(names), "adds": len(plan),
        "gets": len(names), "distinct_entities": len(names),
        "connections": args.conns, "ok": stats["ok"],
        "shed_retried": stats["shed_retried"],
        "attempts": stats["attempts"], "not_ok": stats["other"][:5],
        "n_not_ok": len(stats["other"]), "get_mismatches": wrong[:5],
        "n_get_mismatches": len(wrong),
        "intended_sum": sum(intended.values()),
        "final_total": float(final["value"]),
        "adds_seconds": round(t_adds, 3), "total_seconds": round(t_all, 3),
        "backend_initialised": bool(xb.backends_are_initialized()),
        "totals": want}
    with open(args.out, "w") as f:
        json.dump(result, f)
    return 0


# ================================================================== worker
def run_twice(system, steps: int) -> list:
    """`run(steps)` twice, each to completion; seconds of each (the first
    includes the compile)."""
    t = []
    for _ in range(2):
        t0 = time.monotonic()
        system.run(steps)
        system.block_until_ready()
        t.append(round(time.monotonic() - t0, 3))
    return t


class Worker:
    """The process that holds the device: runs its phases in order, prints
    one JSON line per phase, and writes the summary the parent reads."""

    def __init__(self, tiny: bool, n_devices: int, workdir: str):
        import jax

        from akka_tpu.utils.compile_cache import enable_compile_cache

        self.cache_dir = enable_compile_cache()
        self.size = TINY if tiny else FULL
        self.tiny = tiny
        self.n_devices = n_devices
        self.workdir = workdir
        self.jax = jax
        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        if not tiny:
            check(self.device["platform"] == "tpu",
                  f"no accelerator: jax.devices()[0].platform is "
                  f"{self.device['platform']!r}, not 'tpu'")
        check(len(devs) >= n_devices,
              f"{n_devices} devices wanted, {len(devs)} found")
        # compile accounting straight from JAX's own monitoring events:
        # seconds inside backend compiles (a persistent-cache hit is inside
        # that bracket too, so warm compiles show as short ones), hits, and
        # entries written (jax records a "miss" when it WRITES an entry)
        self.ev = {"compile_s": 0.0, "compiles": 0, "cache_hits": 0,
                   "cache_writes": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.ev["compile_s"] += secs
            self.ev["compiles"] += 1

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.ev["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.ev["cache_writes"] += 1

    # ---------------------------------------------------------- reporting
    def memory(self):
        out = []
        for d in self.jax.devices()[: max(1, self.n_devices)]:
            ms = d.memory_stats() or {}
            out.append({"bytes_in_use": ms.get("bytes_in_use"),
                        "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
        return out

    def run_phase(self, name: str) -> dict:
        fn = PHASES[name]
        note(f"phase {name} starts")
        before = dict(self.ev)
        t0 = time.monotonic()
        info = fn(self)
        wall = time.monotonic() - t0
        ev = {k: self.ev[k] - before[k] for k in before}
        rec = {"phase": name, **self.device, "wall_s": round(wall, 3),
               **ev, "compile_s": round(ev["compile_s"], 3),
               "run_s": round(wall - ev["compile_s"], 3),
               # peak_bytes_in_use is this process's high-water mark so far
               "memory": self.memory(), **info}
        print(json.dumps(rec), flush=True)
        return rec

    def kernel_family(self, m: int, n: int) -> dict:
        """What `auto` resolves to on this platform (ops/segment.py's
        trace-time choices): the reduce kernel for a [m]-message,
        [n]-actor delivery, the slots family, the rank strategy."""
        from akka_tpu.ops import segment as sg
        plat = self.jax.default_backend()
        return {"auto_mode": sg.choose_reduce_kernel(m, n, 4, plat),
                "auto_slots_family": sg._backend_impl(None, plat),
                "auto_rank": sg._auto_rank_strategy(m, n, plat)}

    def exchange_bucketing(self, backend) -> str:
        """The kernel family the mesh runtime buckets its exchange with."""
        from akka_tpu.ops.segment import exchange_uses_ranked
        ranked = exchange_uses_ranked(self.jax.default_backend(), backend)
        return "ranked" if ranked else "wide"

    # ------------------------------------------------------------- phases
    def ring(self, static: bool) -> dict:
        from akka_tpu.models.baseline_benches import (build_ring,
                                                      seed_ring_full)
        n, steps = self.size["n_actors"], self.size["steps"]
        s = build_ring(n, static=static)
        seed_ring_full(s)
        t = run_twice(s, steps)
        recv = s.read_state("received")
        check(recv.shape == (n,) and bool((recv == 2 * steps).all()),
              f"ring(static={static}): received != {2 * steps} on "
              f"{int((recv != 2 * steps).sum())} of {n} rows")
        info = {"actors": n, "steps": 2 * steps, "run_seconds": t,
                "native_stager": s._stager is not None,
                "check": f"received == {2 * steps} on all {n} rows"}
        if static:
            info["delivery"] = f"static topology ({s.topology.kind})"
        else:
            info["delivery"] = self.kernel_family(s.inbox_dst.shape[0], n)
            info["prefix_sum"] = self.prefix_sum_at_the_edge(
                s.inbox_dst.shape[0])
        return info

    def prefix_sum_at_the_edge(self, m: int) -> dict:
        """The merge delivery's running sum (`ops/prefix.py`) of an f32
        column of `m` small integers whose total is 2^24 - 1, the last
        integer its envelope holds, against numpy on the host: on a TPU the
        dots run through the matrix unit's HIGHEST path, which no CPU test
        reaches."""
        import numpy as np

        from akka_tpu.ops.prefix import EXACT_BELOW, prefix_sum
        total = EXACT_BELOW - 1
        col = np.full(m, total // m, np.int64)
        col[np.random.default_rng(m).choice(m, total % m,
                                            replace=False)] += 1
        got = np.asarray(self.jax.jit(prefix_sum)(
            self.jax.numpy.asarray(col, self.jax.numpy.float32)))
        wrong = int((got.astype(np.int64) != np.cumsum(col)).sum())
        check(wrong == 0, f"prefix_sum: {wrong} of {m} running sums of an "
              f"f32 column totalling {total} differ from numpy's")
        return {"rows": m, "total": total, "largest_value": int(col.max()),
                "check": "every running sum equals numpy's int64 cumsum"}

    def fan_in(self) -> dict:
        import numpy as np

        from akka_tpu.models.baseline_benches import build_fan_in
        n_leaves, steps = self.size["n_actors"], self.size["steps"]
        n_coll = self.size["fan_collectors"]
        out = {}
        for static in (True, False):
            s = build_fan_in(n_leaves=n_leaves, n_collectors=n_coll,
                             static=static)
            # every leaf tells the collector its state names one message a
            # step, first column 1, second its `reading_a`; always-on
            # leaves emit every step and deliveries lag one step
            wired = s.read_state("collector")[n_coll:]
            per_coll = np.bincount(wired, minlength=n_coll)
            a_sum = np.bincount(wired, minlength=n_coll,
                                weights=s.read_state("reading_a")[n_coll:])
            t = run_twice(s, steps)
            want = {"msgs": per_coll * (2 * steps - 1),
                    "sum0": per_coll * (2 * steps - 1),
                    "sum1": a_sum.astype(np.int64) * (2 * steps - 1)}
            for col, exact in want.items():
                got = s.read_state(col)[:n_coll]
                check(bool((got == exact).all()),
                      f"fan-in(static={static}): {col} wrong on "
                      f"{int((got != exact).sum())} of {n_coll} collectors")
            key = "static" if static else "dynamic"
            out[key] = {"run_seconds": t, "delivery": (
                f"static topology ({s.topology.kind})" if static else
                self.kernel_family(s.inbox_dst.shape[0], s.capacity))}
        return {"leaves": n_leaves, "collectors": n_coll,
                "steps": 2 * steps, **out,
                "check": "per-collector msgs and int32 sums of columns 0 "
                         f"and 1 exact, {n_leaves * (2 * steps - 1)} tells"}

    def router_pool(self) -> dict:
        """Phase r: config 4, producers telling ONE router ref, the step's
        route stage spreading their tells round-robin over the pool; every
        routee column, the router's counters and the inbox left, exact
        against the plain reference (benchmark/reference/router.py)."""
        from akka_tpu.models.baseline_benches import (
            build_router_pool, router_pool_left_behind)
        from benchmark.reference import router as ref
        n_prod, steps = self.size["n_actors"], self.size["steps"]
        n_routees = self.size["pool_routees"]
        with open(os.path.join(os.path.dirname(HERE), "benchmark", "traffic",
                               "router-random.json")) as f:
            traffic = json.load(f)
        producers = ref.seed_producers(n_prod, n_routees, 22, traffic)
        s = build_router_pool(n_prod, n_routees, producers=producers,
                              mask_period=traffic["mask_period"])
        t = run_twice(s, steps)
        got = router_pool_left_behind(s)
        counters = got["router"]
        numbers = ref.judge(2 * steps, producers, traffic, n_routees,
                            "round-robin", got, dict.fromkeys(
                                ("routees_wrong", "balance_over_one",
                                 "router_counter_wrong", "producers_wrong",
                                 "tokens_wrong", "messages_dropped"), 0))
        for name, c in numbers.items():
            check(c["value"] == 0, f"router pool: {name} = {c['value']}")
        hits = got["routees"]["hits"]
        check(int(hits.sum()) == counters["routed"] > 0,
              "router pool: the routees' hits do not add up to `routed`")
        return {"producers": n_prod, "routees": n_routees, "steps": 2 * steps,
                "run_seconds": t, "routed": counters["routed"],
                "delivery": self.kernel_family(s.inbox_dst.shape[0],
                                               s.capacity),
                "check": "every routee column, next, routed and the inbox "
                         "left equal the reference; hits differ by "
                         f"{int(hits.max() - hits.min())} <= 1"}

    def bank(self) -> dict:
        """Phase s: ordered mailboxes, tellers telling bank-account entities
        Deposit / Withdraw-if-sufficient through slots delivery, the fold
        and the spill; every account column and the queue left (the spill
        region with it), exact against the plain reference
        (benchmark/reference/bank.py) replayed from the seed, and the spill
        counters equal to the reference's count of what was carried over."""
        from akka_tpu.models.baseline_benches import (bank_left_behind,
                                                      build_bank)
        from benchmark.reference import bank as ref
        here = os.path.join(os.path.dirname(HERE), "benchmark")
        with open(os.path.join(here, "traffic", "bank-commands.json")) as f:
            traffic = json.load(f)
        n_tellers = self.size["n_actors"]
        n_accounts, slots, spill = self.size["bank"]
        steps = traffic["chunk_steps"]  # the program the cell's window runs
        tellers = ref.seed_tellers(n_tellers, n_accounts, 23, traffic)
        s = build_bank(n_tellers, n_accounts, slots, spill, tellers=tellers,
                       period=traffic["period"], levels=traffic["levels"])
        got = {}
        t = run_twice(s, steps)
        got["open"] = got["close"] = bank_left_behind(s, n_accounts)
        s.run(steps)
        got["after"] = bank_left_behind(s, n_accounts)
        got["dropped"] = got["after"]["dropped"]
        with open(os.path.join(here, "configs",
                               "bank-accounts-128k.json")) as f:
            limits = json.load(f)["limits"]  # every one 0
        limits.pop("compiles_in_window")
        numbers = ref.judge(tellers, traffic, n_accounts, slots, got, limits)
        for name, c in numbers.items():
            check(c["value"] == 0, f"bank: {name} = {c['value']}")
        _, _, carried = ref.replay(ref.zero_accounts(n_accounts),
                                   ref.empty_queue(), 0, 3 * steps, tellers,
                                   traffic, slots)
        counters = (got["after"]["spilled"], got["after"]["spill_high_water"])
        check(counters == (int(carried.sum()), int(carried.max())),
              f"bank: spilled / spill_high_water {counters} are not the "
              f"reference's {int(carried.sum())} / {int(carried.max())}")
        check(carried.sum() > 0, "bank: no mailbox overflowed")
        acc = got["after"]["accounts"]
        return {"tellers": n_tellers, "accounts": n_accounts,
                "mailbox_slots": slots, "steps": 3 * steps, "run_seconds": t,
                "applied": int(acc["applied"].sum()),
                "rejected": int(acc["rejected"].sum()),
                "spilled": counters[0], "spill_high_water": counters[1],
                "slots_family": self.kernel_family(
                    s.inbox_dst.shape[0], s.capacity)["auto_slots_family"],
                "check": "every account column and the queue left equal the "
                         "reference's replay from the seed; nothing dropped"}

    def host_tells(self) -> dict:
        """Phase d: ActorSystem + tpu-batched default dispatcher + Props."""
        import jax.numpy as jnp
        import numpy as np

        from akka_tpu import ActorSystem
        from akka_tpu.batched import (Emit, behavior, device_props,
                                      get_handle, reply_dst)
        sz = self.size
        disp = {"type": "tpu-batched"}  # defaults: 1M rows, depth-2 pipeline
        if self.tiny:
            disp.update({"capacity": 1 << 11, "promise-rows": 16})
        system = ActorSystem.create("smoke-d", {"akka": {
            "stdout-loglevel": "OFF", "log-dead-letters": 0,
            "actor": {"default-dispatcher": disp}}})
        try:
            handle = get_handle(system)
            P = handle.payload_width

            @behavior("smoke_acc", {"total": ((), jnp.float32),
                                    "msgs": ((), jnp.int32)})
            def acc(state, inbox, ctx):
                total = state["total"] + inbox.sum[0]
                reply = jnp.zeros((P,), jnp.float32).at[0].set(total)
                # only an ask carries a reply-to row (> 0) in the last column
                return ({"total": total,
                         "msgs": state["msgs"] + inbox.count},
                        Emit.single(reply_dst(inbox.sum), reply, 1, P,
                                    when=inbox.sum[-1] > 0))

            n = sz["tell_actors"]
            block = system.actor_of(device_props(acc, n=n), "acc")
            t0 = time.monotonic()
            rt = handle.runtime  # builds + warms the step programs
            build_s = time.monotonic() - t0

            rng = np.random.default_rng(22)
            n_tell_rows = n - sz["asks"]  # the last rows only take asks
            dst = rng.integers(0, n_tell_rows, size=sz["tells"])
            val = rng.integers(1, 6, size=sz["tells"]).astype(np.float32)
            oracle = np.zeros((n,), np.float64)
            np.add.at(oracle, dst, val)
            count = np.bincount(dst, minlength=n).astype(np.int64)

            T, R = sz["tell_threads"], sz["tell_rounds"]
            gate = threading.Barrier(T + 1)
            errors = []

            def teller(k: int) -> None:
                try:
                    mine = np.arange(k, sz["tells"], T)
                    for chunk in np.array_split(mine, R):
                        gate.wait(120)
                        for i in chunk:
                            block[int(dst[i])].tell([float(val[i])])
                except BaseException as e:  # noqa: BLE001 — reported below
                    errors.append(repr(e))
                    gate.abort()

            threads = [threading.Thread(target=teller, args=(k,))
                       for k in range(T)]
            for t in threads:
                t.start()
            ask_rows = list(range(n_tell_rows, n))
            ask_at = {int(r * R / len(ask_rows)): row
                      for r, row in enumerate(ask_rows)}
            t0 = time.monotonic()
            for r in range(R):
                seen = handle.pipeline_stats()["steps"]
                gate.wait(120)  # release round r: tells land while older
                #                 step programs may still be in flight
                if r in ask_at:
                    row = ask_at[r]
                    got = block[row].ask_sync([float(r + 1)], timeout=120.0)
                    oracle[row] += r + 1
                    count[row] += 1
                    check(float(got[0]) == oracle[row],
                          f"ask_sync row {row}: reply {got[0]} != "
                          f"{oracle[row]}")
                deadline = time.monotonic() + 120
                while handle.pipeline_stats()["steps"] <= seen:
                    check(time.monotonic() < deadline,
                          "pump dispatched no step in 120 s")
                    time.sleep(0.0005)
            for t in threads:
                t.join(120)
            check(not errors and not any(t.is_alive() for t in threads),
                  f"teller threads failed: {errors[:3]}")
            # stager order is FIFO: once this ask is answered, every tell
            # staged before it has been delivered
            last = ask_rows[-1]
            got = block[last].ask_sync([0.0], timeout=120.0)
            count[last] += 1
            check(float(got[0]) == oracle[last], "closing ask_sync reply")
            tell_s = time.monotonic() - t0

            total = block.read_state("total").astype(np.float64)
            msgs = block.read_state("msgs").astype(np.int64)
            stats = handle.pipeline_stats()
            check(bool((total == oracle).all()),
                  f"host tells: total != oracle on "
                  f"{int((total != oracle).sum())} of {n} rows")
            check(bool((msgs == count).all()),
                  f"host tells: msgs != oracle on "
                  f"{int((msgs != count).sum())} of {n} rows")
            check(rt.dropped_messages == 0 and rt.dead_lettered == 0,
                  f"host tells dropped={rt.dropped_messages} "
                  f"dead_lettered={rt.dead_lettered}")
            check(stats["steps"] >= 32,
                  f"tells spread over {stats['steps']} steps, < 32")
            info = {"capacity": rt.capacity, "device_actors": n,
                    "tells": sz["tells"], "tell_threads": T,
                    "ask_sync": len(ask_rows) + 1,
                    "steps": stats["steps"],
                    "pipeline_depth": stats["depth"],
                    "build_and_warm_s": round(build_s, 3),
                    "tell_seconds": round(tell_s, 3),
                    "native_stager": rt._stager is not None,
                    "delivery": rt._core.delivery,
                    "check": f"per-row total and msgs == numpy oracle on "
                             f"all {n} rows; {int(count.sum())} messages"}
        finally:
            system.terminate()
            check(system.await_termination(60.0),
                  "ActorSystem did not terminate")
        return info

    def cross_shard(self) -> dict:
        """Phase e: BASELINE config 5 raw, every tell crosses the mesh."""
        from akka_tpu.models.baseline_benches import (build_cross_shard,
                                                      seed_ring_full)
        sz = self.size
        steps = sz["steps"]
        s = build_cross_shard(sz["shards"], sz["eps"],
                              n_devices=self.n_devices)
        seed_ring_full(s)
        t = run_twice(s, steps)
        held = self.devices_holding(s.state["received"])
        recv = s.read_state("received")
        check(bool((recv == 2 * steps).all()),
              f"cross-shard: received != {2 * steps} on "
              f"{int((recv != 2 * steps).sum())} of {s.capacity} rows")
        check(s.total_dropped == 0,
              f"cross-shard: total_dropped == {s.total_dropped}")
        return {"shards": sz["shards"], "entities_per_shard": sz["eps"],
                "actors": s.capacity, "steps": 2 * steps, "run_seconds": t,
                "state_on_devices": held,
                "delivery": self.kernel_family(s.m_local, s.local_n),
                "exchange_bucketing": self.exchange_bucketing(None),
                "native_stager": False,  # the mesh runtime stages in Python
                "check": f"received == {2 * steps} on all rows, "
                         "total_dropped == 0"}

    def devices_holding(self, arr) -> int:
        """Evidence that state lives on `n_devices` devices, not the first:
        the array's sharding spans them and each reports memory in use."""
        held = len(arr.sharding.device_set)
        check(held == self.n_devices,
              f"state spans {held} devices, wanted {self.n_devices}")
        for d in arr.sharding.device_set:
            ms = d.memory_stats()
            if ms is not None:  # the CPU backend reports none
                check(ms.get("bytes_in_use", 0) > 0,
                      f"device {d.id} holds no bytes")
        return held

    def served(self) -> dict:
        """The served path, the stack a deployment runs composed: evloop
        front door + cross-connection aggregation + batched ask waves +
        durable entity journal (commit before ack) + reply-cache dedup;
        then the same traffic again over continuous wave formation."""
        from akka_tpu import ActorSystem
        from akka_tpu.gateway import counter_behavior
        from akka_tpu.sharding import JournalRememberEntitiesStore
        from akka_tpu.sharding.device import DeviceEntity, DeviceShardRegion
        sz = self.size
        d = tempfile.mkdtemp(prefix="served_", dir=self.workdir)
        system = ActorSystem("gateway", {"akka": {
            "stdout-loglevel": "OFF", "metrics": {"enabled": True}}})
        legs = {}
        try:
            t0 = time.monotonic()
            spec = DeviceEntity("counter", counter_behavior(4),
                                n_shards=sz["shards"],
                                entities_per_shard=sz["eps"],
                                n_devices=self.n_devices, payload_width=4)
            spec.remember_store = JournalRememberEntitiesStore(
                os.path.join(d, "remember_entities.journal"))
            region = DeviceShardRegion(spec)
            region.attach_journal(d, fsync_every_n=1)
            region.attach_entity_journal(d, fsync_every_n=1,
                                         registry=system.metrics_registry)
            region.checkpoint()
            build_s = time.monotonic() - t0
            note(f"served: region built and checkpointed in {build_s:.1f} s")
            held = self.devices_holding(region.system.state["total"])
            base = {}
            for leg, continuous in (("serialized", False),
                                    ("continuous", True)):
                legs[leg], base = self.serve_leg(system, region,
                                                 continuous, base)
            info = {"shards": sz["shards"], "entities_per_shard": sz["eps"],
                    "entity_rows": sz["shards"] * sz["eps"],
                    "capacity": region.system.capacity,
                    "promise_pool": region.eps,
                    "n_devices": self.n_devices, "state_on_devices": held,
                    "region_build_s": round(build_s, 3),
                    "delivery": region.system._core.delivery,
                    "exchange_bucketing": self.exchange_bucketing(
                        spec.delivery_backend),
                    "native_stager": False,  # as in phase e
                    **legs,
                    "check": "every request ok, per-entity get == dict "
                             "oracle, final_total == intended_sum, both legs"}
        finally:
            system.terminate()
            check(system.await_termination(60.0),
                  "gateway ActorSystem did not terminate")
        return info

    def serve_leg(self, system, region, continuous: bool,
                  base: dict) -> tuple:
        """One gateway over `region` and one run of the load generator
        against it. `base`: the per-entity totals the previous leg left.
        Returns (the leg's record, the totals now)."""
        from akka_tpu.gateway import (AdmissionController, GatewayClient,
                                      GatewayServer, RegionBackend,
                                      ReplyCacheTable, SloTracker)
        sz = self.size
        backend = RegionBackend(region, continuous=continuous)
        admission = AdmissionController(
            rate=1e6, burst=1e6,
            pressure_signals=backend.pressure_signals(),
            thresholds={"ask_pool_occupancy": 0.9, "mailbox_overflow": 0.0,
                        "exchange_dropped": 0.0},
            metrics_registry=system.metrics_registry)
        slo = SloTracker(registry=system.metrics_registry)
        dedup = ReplyCacheTable(window=4096)
        server = GatewayServer(system, backend, admission, slo, port=0,
                               transport="evloop", aggregate=True,
                               dedup=dedup)
        host, port = server.start()
        # one window over the socket before the load starts, so this
        # engine's step programs compile here and not inside a client's
        # socket timeout: two gets of ONE fresh entity in one frame ride one
        # wave, the second deferred behind the first, which runs both the
        # opening multi-step program and the single-step one. A get adds
        # nothing to any sum.
        t0 = time.monotonic()
        warm = GatewayClient(host, port, timeout=900.0)
        try:
            reps = warm.request_many([("tenant0", "warm-up", "get", 0.0)] * 2)
        finally:
            warm.close()
        check([(r.get("status"), r.get("value")) for r in reps]
              == [("ok", 0.0)] * 2, f"warm-up window: {reps}")
        warm_s = time.monotonic() - t0
        note(f"served[{'continuous' if continuous else 'serialized'}]: "
             f"warm-up window answered in {warm_s:.1f} s")
        out = os.path.join(self.workdir, f"load_{int(continuous)}.json")
        base_file = out + ".base"
        with open(base_file, "w") as f:
            json.dump(base, f)
        cmd = [sys.executable, HERE, "--load", "--port", str(port),
               "--conns", str(sz["conns"]), "--entities", str(sz["entities"]),
               "--adds", str(sz["adds"]), "--tenants", str(sz["tenants"]),
               "--seed", "22", "--out", out, "--base", base_file]
        t0 = time.monotonic()
        # the generator needs no device: the chip is this process's
        child = subprocess.Popen(cmd, env=dict(os.environ,
                                               JAX_PLATFORMS="cpu"),
                                 start_new_session=True)
        try:
            rc = child.wait(timeout=600 if not self.tiny else 240)
        finally:
            kill_group(child)
        load_s = time.monotonic() - t0
        try:
            check(rc == 0, f"load generator exited {rc}")
            with open(out) as f:
                res = json.load(f)
            totals = res.pop("totals")
            ev = server._evloop.stats()
            journal = region._entity_journal.stats()
            batch = backend.batcher.stats()
            total_now = backend.sum_all()
        finally:
            server.stop()
            backend.close()
        want_sum = sum(totals.values())
        check(not res["backend_initialised"],
              "the load generator initialised a JAX backend")
        check(res["n_not_ok"] == 0 and res["ok"] == res["requests"],
              f"{res['n_not_ok']} requests not ok: {res['not_ok']}")
        check(res["connections"] >= (16 if not self.tiny else 2)
              and res["distinct_entities"] >= (10000 if not self.tiny else 2),
              "load too small")
        check(res["n_get_mismatches"] == 0,
              f"{res['n_get_mismatches']} entities differ from the oracle: "
              f"{res['get_mismatches']}")
        check(res["final_total"] == want_sum == total_now,
              f"final_total {res['final_total']} / sum_all {total_now} != "
              f"intended {want_sum}")
        check(ev["frames_in"] >= res["requests"],
              f"evloop saw {ev['frames_in']} frames < {res['requests']}")
        check(backend.batcher.continuous == continuous, "wrong wave engine")
        # commit before ack, one group commit per ask wave
        check(journal["fsyncs"] == journal["waves"] > 0
              and journal["events"] >= res["adds"],
              f"entity journal did not group-commit per wave: {journal}")
        leg = {k: res[k] for k in (
            "requests", "adds", "gets", "distinct_entities", "connections",
            "ok", "shed_retried", "attempts", "intended_sum", "final_total",
            "adds_seconds", "total_seconds")}
        leg.update({"warm_up_window_s": round(warm_s, 3),
                    "load_process_s": round(load_s, 3),
                    "load_backend_initialised": res["backend_initialised"],
                    "evloop_frames_in": ev["frames_in"],
                    "journal_so_far": {k: journal[k] for k in
                                       ("waves", "events", "fsyncs",
                                        "replies")},
                    "ask_batch": {k: batch[k] for k in
                                  ("batches", "asks", "mean_batch_size",
                                   "max_batch_size", "overlap_ratio")},
                    "intended_sum_so_far": want_sum})
        return leg, totals


PHASES = {
    "a": lambda w: w.ring(static=True),
    "b": lambda w: w.ring(static=False),
    "c": Worker.fan_in,
    "r": Worker.router_pool,
    "s": Worker.bank,
    "d": Worker.host_tells,
    "e": Worker.cross_shard,
    "served": Worker.served,
}


def run_worker(args: argparse.Namespace) -> int:
    w = Worker(args.tiny, args.chips, os.path.dirname(args.out))
    recs = [w.run_phase(name) for name in args.worker.split(",")]
    with open(args.out, "w") as f:
        json.dump({"device": w.device, "cache_dir": w.cache_dir,
                   "phases": recs}, f)
    return 0


# ================================================================== parent
def kill_group(proc: subprocess.Popen) -> None:
    """Stop a child started in its own session, and whatever it started."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def run_parent(args: argparse.Namespace) -> int:
    if args.chips > 1:
        plan = ["e,served"]
    else:
        plan = ["a,b,c,r,s,d", "a,served"]
    deadline = time.monotonic() + TIME_LIMIT_S - 30.0
    print(f"[chip_smoke] compile cache: {compile_cache_dir()} "
          f"(JAX_COMPILATION_CACHE_DIR "
          f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})",
          flush=True)
    runs = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        for i, phases in enumerate(plan):
            out = os.path.join(tmp, f"worker{i}", "result.json")
            os.makedirs(os.path.dirname(out))
            cmd = [sys.executable, HERE, "--worker", phases, "--out", out,
                   "--chips", str(args.chips)] + (["--tiny"] * args.tiny)
            print(f"[chip_smoke] worker {i}: phases {phases}", flush=True)
            proc = subprocess.Popen(cmd, start_new_session=True)
            try:
                rc = proc.wait(timeout=max(1.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                print(f"[chip_smoke] FAILED: worker {i} ({phases}) overran "
                      f"the {TIME_LIMIT_S:.0f} s limit", file=sys.stderr)
                return 1
            finally:
                kill_group(proc)
            if rc != 0:
                print(f"[chip_smoke] FAILED: worker {i} ({phases}) exited "
                      f"{rc}", file=sys.stderr)
                return 1
            with open(out) as f:
                runs.append(json.load(f))
    # the phase that ran in two processes: was its second compile warm?
    first = {}
    for run in runs:
        for rec in run["phases"]:
            cold = first.setdefault(rec["phase"], rec)
            if cold is rec:
                continue
            print(f"[chip_smoke] compile cache, phase {rec['phase']} run "
                  f"twice: first process {cold['compile_s']} s "
                  f"({cold['cache_hits']} hits, {cold['cache_writes']} "
                  f"written), second process {rec['compile_s']} s "
                  f"({rec['cache_hits']} hits, {rec['cache_writes']} "
                  f"written)", flush=True)
            if cold["cache_writes"] > 0 and rec["cache_hits"] == 0:
                print("[chip_smoke] FAILED: the first process wrote cache "
                      "entries and the second hit none", file=sys.stderr)
                return 1
    assert "jax" not in sys.modules, "the parent must stay off JAX"
    print(json.dumps({"ok": True, "device": runs[0]["device"]}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="devices one worker drives (4: phases e + served)")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes; the only mode allowed off the TPU")
    ap.add_argument("--worker", help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    ap.add_argument("--load", action="store_true", help=argparse.SUPPRESS)
    for name, typ in (("port", int), ("conns", int), ("entities", int),
                      ("adds", int), ("tenants", int), ("seed", int),
                      ("base", str)):
        ap.add_argument(f"--{name}", type=typ, help=argparse.SUPPRESS)
    ap.add_argument("--deadline", type=float, default=300.0,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.load:
        return run_load(args)
    if args.worker:
        return run_worker(args)
    return run_parent(args)


if __name__ == "__main__":
    sys.exit(main())
