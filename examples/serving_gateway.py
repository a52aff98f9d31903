#!/usr/bin/env python
"""Serving gateway example: external traffic in, sharded entities
on-device, SLOs out (docs/SERVING_GATEWAY.md).

Three subcommands compose into a small multi-process serving stack:

  serve  -- one gateway process: framed-TCP front door (stream layer),
            admission control, SLO tracker, and a DeviceShardRegion of
            event-sourced counter entities with an armed WAL +
            checkpoint directory. Prints "READY <port>" once bound.
            `--restore` recovers from the checkpoint dir instead of
            starting fresh (the crash-recovery path).
  load   -- one load-generator process: paced client traffic through
            the front door, reconnecting through server restarts.
            Prints a JSON result line (sent/acked sums, outcome counts).
  demo   -- the orchestrator: spawns a serve child + two load children,
            then injects the three chaos legs over the wire (shard
            rebalance, kill -9 + restore, device failover) and checks
            the conserved-value invariant:

                acked_sum <= final_total <= sent_sum

            Every acknowledged write survives; nothing is double-counted
            beyond what was actually sent.

Run it:   python examples/serving_gateway.py demo [--devices N]

The serve child runs on whatever platform JAX gives it and is the ONE
process that holds the device; `demo` itself never initialises a backend
and its load children run with JAX_PLATFORMS=cpu. No entry point here picks
the CPU on its own — for a CPU run say so from outside:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        python examples/serving_gateway.py demo --devices 2

With one device the 2 -> 1 failover leg is skipped, and the demo says so.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ------------------------------------------------------------------ serve
def cmd_serve(args: argparse.Namespace) -> int:
    from akka_tpu import ActorSystem
    from akka_tpu.gateway import (AdmissionController, GatewayServer,
                                  RegionBackend, SloTracker, counter_behavior)
    from akka_tpu.sharding.device import DeviceEntity, DeviceShardRegion
    from akka_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    system = ActorSystem("gateway", {"akka": {
        "stdout-loglevel": "OFF",
        "metrics": {"enabled": True},
        "persistence": {"tell-journal": {
            "fsync-every-n": args.fsync_every_n}}}})
    spec = DeviceEntity("counter", counter_behavior(4),
                        n_shards=args.shards,
                        entities_per_shard=args.eps,
                        n_devices=args.devices,
                        payload_width=4)
    if args.durable:
        # durable entity layer (docs/DURABLE_ENTITIES.md): remembered ids
        # in a record-log store, per-entity events group-committed at the
        # ask-wave boundary into the entity journal
        from akka_tpu.sharding import JournalRememberEntitiesStore
        spec.remember_store = JournalRememberEntitiesStore(
            os.path.join(args.dir, "remember_entities.journal"))
    region = DeviceShardRegion(spec)
    region.attach_journal(args.dir, fsync_every_n=args.fsync_every_n)
    if args.durable:
        region.attach_entity_journal(
            args.dir, fsync_every_n=args.fsync_every_n,
            registry=system.metrics_registry)
    if args.restore:
        step = region.restore()
        print(f"RESTORED step={step}", flush=True)
        if args.durable:
            replayed = region._durable_replayed_totals or {}
            print(f"DURABLE respawned={len(replayed)} "
                  f"sum={sum(replayed.values()):.1f}", flush=True)
    else:
        region.checkpoint()  # baseline snapshot so crash recovery can start
    backend = RegionBackend(region)
    admission = AdmissionController(
        rate=args.rate, burst=args.burst,
        pressure_signals=backend.pressure_signals(),
        thresholds={"ask_pool_occupancy": 0.9,
                    "mailbox_overflow": 0.0,     # any NEW device mail loss
                    "exchange_dropped": 0.0},
        metrics_registry=system.metrics_registry)
    slo = SloTracker(registry=system.metrics_registry,
                     target_p50_ms=args.target_p50_ms,
                     target_p99_ms=args.target_p99_ms)
    dedup = None
    if args.dedup:
        # exactly-once retry effects (docs/SERVING_GATEWAY.md "Delivery
        # guarantees"): with --durable the ok-reply frontier rides the
        # entity journal's group commit and survives kill -9
        from akka_tpu.gateway import ReplyCacheTable
        dedup = ReplyCacheTable(window=args.dedup_window)
    server = GatewayServer(system, backend, admission, slo,
                           port=args.port, dedup=dedup)
    host, port = server.start()
    print(f"READY {port}", flush=True)

    stop = {"flag": False}

    def _term(signum, frame):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)

    art_path = os.path.join(args.dir, "slo.json")
    try:
        while not stop["flag"]:
            time.sleep(0.25)
            if system.metrics_registry is not None:
                system.metrics_registry.set_step(region.system._host_step)
            # keep a recent artifact on disk so even kill -9 leaves one
            with open(art_path + ".tmp", "w") as f:
                json.dump(slo.artifact(), f)
            os.replace(art_path + ".tmp", art_path)
    finally:
        with open(art_path + ".tmp", "w") as f:
            json.dump(slo.artifact(), f)
        os.replace(art_path + ".tmp", art_path)
        server.stop()
        system.terminate()
    return 0


# ------------------------------------------------------------------- load
def cmd_load(args: argparse.Namespace) -> int:
    from akka_tpu.gateway import GatewayClient

    client = GatewayClient("127.0.0.1", args.port, timeout=10.0)
    deadline = time.monotonic() + args.seconds
    sent_sum = acked_sum = 0.0
    counts = {"ok": 0, "shed": 0, "error": 0, "conn_error": 0}
    i = 0
    while time.monotonic() < deadline:
        i += 1
        entity = f"{args.tenant}-acct-{i % args.entities}"
        value = float(i % 5 + 1)
        # one attempt == one wire send: sent_sum must count every send,
        # including re-sends after a connection death, or the conserved-
        # value upper bound does not hold across crash legs
        sent_sum += value
        try:
            reply = client.request(args.tenant, entity, "add", value)
        except (OSError, ConnectionError, socket.timeout):
            counts["conn_error"] += 1
            client.close()
            time.sleep(args.pause)
            continue
        status = reply.get("status")
        if status == "ok":
            acked_sum += value
            counts["ok"] += 1
        elif status == "shed":
            counts["shed"] += 1
            time.sleep(min(1.0, reply.get("retry_after_ms", 100) / 1e3))
        else:
            counts["error"] += 1
        if args.pace > 0:
            time.sleep(args.pace)
    client.close()
    print(json.dumps({"tenant": args.tenant, "sent_sum": sent_sum,
                      "acked_sum": acked_sum, **counts}), flush=True)
    return 0


# ------------------------------------------------------------------- demo
def _spawn_serve(port: int, directory: str, restore: bool = False,
                 devices: int = 1, durable: bool = False,
                 dedup: bool = False) -> subprocess.Popen:
    # the child inherits the environment as it is: which platform it runs
    # on was decided outside this program
    cmd = [sys.executable, os.path.abspath(__file__), "serve",
           "--port", str(port), "--dir", directory,
           "--devices", str(devices), "--shards", "4", "--eps", "16",
           "--rate", "400", "--burst", "200"]
    if restore:
        cmd.append("--restore")
    if durable:
        cmd.append("--durable")
    if dedup:
        cmd.append("--dedup")
    return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _wait_ready(proc: subprocess.Popen, secs: float = 120.0) -> int:
    deadline = time.monotonic() + secs
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"serve child exited rc={proc.poll()} before READY")
        sys.stdout.write(f"  [serve] {line}")
        if line.startswith("READY "):
            return int(line.split()[1])
    raise TimeoutError("serve child never printed READY")


def _spawn_load(port: int, tenant: str, seconds: float) -> subprocess.Popen:
    # a load generator needs no device: the chip belongs to the serve child
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "load",
         "--port", str(port), "--tenant", tenant,
         "--seconds", str(seconds), "--pace", "0.01"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def cmd_demo(args: argparse.Namespace) -> int:
    import tempfile

    from akka_tpu.gateway import GatewayClient

    directory = args.dir or tempfile.mkdtemp(prefix="gateway_demo_")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    print(f"[demo] checkpoint dir {directory}")
    serve = _spawn_serve(port, directory, devices=args.devices)
    _wait_ready(serve)
    print(f"[demo] gateway up on :{port}; starting 2 load processes")
    loads = [_spawn_load(port, f"tenant{i}", args.seconds) for i in (0, 1)]
    admin = GatewayClient("127.0.0.1", port, timeout=30.0)

    time.sleep(args.seconds * 0.25)
    print("[demo] chaos leg 1: shard rebalance (admin op over the wire)")
    print("  ->", admin.request_retry("__admin", "", "rebalance", 0.0,
                                      deadline_s=60.0))

    time.sleep(args.seconds * 0.2)
    print("[demo] chaos leg 2: kill -9 the gateway, restart with --restore")
    serve.send_signal(signal.SIGKILL)
    serve.wait()
    admin.close()
    serve = _spawn_serve(port, directory, restore=True,
                         devices=args.devices)
    _wait_ready(serve)

    time.sleep(args.seconds * 0.2)
    if args.devices >= 2:
        print(f"[demo] chaos leg 3: device failover ({args.devices} -> 1 "
              "survivor)")
        print("  ->", admin.request_retry("__admin", "", "failover", 1.0,
                                          deadline_s=60.0))
    else:
        print("[demo] chaos leg 3: device failover SKIPPED (one device: "
              "nothing to fail over to; pass --devices N)")

    results = []
    for p in loads:
        out = p.communicate()[0]
        for line in out.splitlines():
            try:
                results.append(json.loads(line))
            except ValueError:
                sys.stdout.write(f"  [load] {line}\n")
    sent = sum(r["sent_sum"] for r in results)
    acked = sum(r["acked_sum"] for r in results)

    final = admin.request_retry("__admin", "", "sum", deadline_s=60.0)
    artifact = admin.request_retry("__admin", "", "artifact",
                                   deadline_s=60.0)["data"]
    admin.close()
    serve.send_signal(signal.SIGTERM)
    try:
        serve.wait(timeout=30)
    except subprocess.TimeoutExpired:
        serve.kill()

    total = float(final["value"])
    ok = acked <= total + 1e-6 and total <= sent + 1e-6
    print(json.dumps({"sent_sum": sent, "acked_sum": acked,
                      "final_total": total, "invariant_held": ok,
                      "p50_ms": artifact["p50_ms"],
                      "p99_ms": artifact["p99_ms"],
                      "reject_rate": artifact["reject_rate"],
                      "requests": artifact["requests"]}, indent=2))
    if not ok:
        print("[demo] CONSERVED-VALUE INVARIANT VIOLATED", file=sys.stderr)
        return 1
    print("[demo] invariant held: acked <= final <= sent")
    return 0


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    s = sub.add_parser("serve", help="run one gateway process")
    s.add_argument("--port", type=int, default=0)
    s.add_argument("--dir", required=True,
                   help="checkpoint + WAL directory")
    s.add_argument("--restore", action="store_true")
    s.add_argument("--shards", type=int, default=4)
    s.add_argument("--eps", type=int, default=16)
    s.add_argument("--devices", type=int, default=None)
    s.add_argument("--rate", type=float, default=200.0)
    s.add_argument("--burst", type=float, default=100.0)
    s.add_argument("--fsync-every-n", type=int, default=1)
    s.add_argument("--durable", action="store_true",
                   help="entity journal + durable remember-entities")
    s.add_argument("--dedup", action="store_true",
                   help="journaled reply-cache dedup (exactly-once "
                        "retry effects; pair with --durable to survive "
                        "kill -9)")
    s.add_argument("--dedup-window", type=int, default=4096,
                   help="remembered request ids per tenant")
    s.add_argument("--target-p50-ms", type=float, default=50.0)
    s.add_argument("--target-p99-ms", type=float, default=500.0)

    l = sub.add_parser("load", help="run one load-generator process")
    l.add_argument("--port", type=int, required=True)
    l.add_argument("--tenant", default="tenant0")
    l.add_argument("--entities", type=int, default=8)
    l.add_argument("--seconds", type=float, default=10.0)
    l.add_argument("--pace", type=float, default=0.01)
    l.add_argument("--pause", type=float, default=0.2)

    d = sub.add_parser("demo", help="3-process demo with chaos legs")
    d.add_argument("--seconds", type=float, default=20.0)
    d.add_argument("--dir", default=None)
    d.add_argument("--devices", type=int, default=1,
                   help="devices the serve child spans (>= 2 enables the "
                        "failover leg)")

    args = ap.parse_args(argv)
    return {"serve": cmd_serve, "load": cmd_load,
            "demo": cmd_demo}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
