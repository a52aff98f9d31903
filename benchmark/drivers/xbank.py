"""Driver of the sharded bank's cell: the deployment built by
`akka_tpu.models.baseline_benches.build_bank_sharded` over the run's chips,
stepped by `run(k)` in chunks.

As benchmark/drivers/bank.py, whose docstring holds for this file too: the
tellers are drawn from the seed by the reference and handed to the builder,
every teller tells one command every step, tells are counted by the
accounts' own `applied` column, and the system is read three times (after
the warm chunks, when the window has closed, after one more chunk through
the same executable). What differs is the cluster: the builder is told the
number of chips, a reading holds every state column BY ROW (the reference
places accounts and tellers by its own arithmetic, never the builder's),
the inbox is a block a chip, and the device's counters of what the spill
regions and the exchange carried and lost are read with it
(benchmark/reference/bank_sharded.py::judge). The timed loop is the fifth
copy of drivers/ring.py's (ROADMAP.md C10)."""

from __future__ import annotations

import time

import numpy as np

from benchmark.drivers.bank import RULE
from benchmark.reference import bank_sharded as reference

# what the configuration states beside the builder's arguments
SHAPE = {"mailbox_slots": "mailbox_slots", "spill_capacity": "spill_capacity",
         "remote_capacity_per_pair": "remote_capacity_per_pair",
         "n_shards": "logical_shards", "period": "period", "levels": "levels"}


def _build(run, tellers):
    from akka_tpu.models import baseline_benches as bb

    conf = run.config
    for key in RULE:
        if conf[key] != run.traffic[key]:
            raise ValueError(f"{key}: the configuration states {conf[key]}, "
                             f"the traffic tells {run.traffic[key]}")
    args = dict(conf["builder_args"])
    for key, stated in SHAPE.items():
        if args[key] != conf[stated]:
            raise ValueError(f"{key}: the builder's differs from the "
                             f"configuration's {stated}")
    args[conf["n_devices_arg"]] = len(run.devices)
    builder = getattr(bb, conf["builder"], None)
    if builder is None:  # a program from before the configuration
        from benchmark.harness import BenchError
        raise BenchError(f"the program has no builder {conf['builder']!r} "
                         f"(akka_tpu.models.baseline_benches): it cannot "
                         f"run the configuration {conf['name']!r}")
    system = builder(tellers=tellers, **args)
    rows = int(system.inbox_dst.shape[0]) // len(run.devices)
    if rows != conf["inbox_rows_per_chip"]:
        raise ValueError(f"inbox_rows_per_chip: the configuration states "
                         f"{conf['inbox_rows_per_chip']}, the builder lays "
                         f"out {rows}")
    return system


def _applied(system) -> int:
    """The commands applied so far (waits for every chunk dispatched); the
    tellers' rows hold 0 in that column."""
    return int(np.asarray(system.read_state("applied"), np.int64).sum())


def _reading(system, with_tellers: bool = False) -> dict:
    """Every column by row, the inbox, the step count and the device's
    counters, as host copies."""
    columns = reference.ACCOUNT_COLUMNS + (
        reference.TELLER_COLUMNS if with_tellers else ())
    return {"state": {k: system.read_state(k) for k in columns},
            "inbox_dst": np.asarray(system.inbox_dst),
            "inbox_type": np.asarray(system.inbox_type),
            "inbox_payload": np.asarray(system.inbox_payload),
            "inbox_valid": np.asarray(system.inbox_valid),
            "steps": int(np.asarray(system.step_count)),
            "counters": {"mail_dropped": int(system.mailbox_overflow),
                         "exchange_dropped": int(system.total_dropped),
                         **system.read_mesh_stats()}}


def _setup_of_the_program() -> dict:
    """Set-up as the program's own compile log holds it so far (one row a
    program compiled or loaded): which of tracing and lowering, compiling
    and loading holds a `setup_s`, and whether the run was cold."""
    from akka_tpu.event.flight_recorder import compile_log

    rows = compile_log()
    return {"programs": len(rows),
            "trace_lower_s": round(sum(r["trace_s"] + r["lower_s"]
                                       for r in rows), 3),
            "compile_s": round(sum(r["compile_s"] for r in rows), 3),
            "cache_misses": sum(1 for r in rows if r["cache"] == "miss")}


def run(run) -> None:
    conf, traffic = run.config, run.traffic
    dep = reference.from_config(conf, len(run.devices))
    t0 = time.monotonic()
    tellers = reference.seed_tellers(dep.n_tellers, dep.n_accounts, run.seed,
                                     traffic)
    system = _build(run, tellers)
    run.notes["build_s"] = round(time.monotonic() - t0, 3)

    if "xbank_step" in run.faults:  # tests plant a fault under the timed path
        run.faults["xbank_step"](system)

    chunk = int(traffic["chunk_steps"])
    t0 = time.monotonic()
    for _ in range(int(traffic["warm_chunks"])):
        system.run(chunk)
        system.block_until_ready()
    got = {"open": _reading(system)}
    before = int(np.asarray(got["open"]["state"]["applied"], np.int64).sum())
    run.notes["warm_s"] = round(time.monotonic() - t0, 3)
    run.notes["setup_program"] = _setup_of_the_program()

    trace = run.device_trace
    trace_from = float(traffic["trace_after_seconds"])
    trace_for = float(traffic["trace_seconds"])
    traced_steps = 0
    # Two chunks in flight: the next is enqueued before the last is waited
    # for (the step count is the program's own sync, a non-donated output),
    # so the device does not wait while the host comes back from a sync. A
    # traced run keeps one in flight, so the stretch holds whole chunks.
    depth = 1 if trace is not None else 2
    in_flight = []
    synced = []  # when each wait for a chunk came back
    run.t_open = t_open = time.monotonic()
    deadline = t_open + run.seconds
    while True:
        now = time.monotonic()
        if now >= deadline:
            break
        if trace is not None and trace.path is None:
            if trace.t_a is None and now - t_open >= trace_from:
                trace.start()
            elif trace.t_a is not None and now - trace.t_a >= trace_for:
                trace.stop()
        system.run(chunk)
        in_flight.append(system.step_count)
        if len(in_flight) >= depth:
            np.asarray(in_flight.pop(0))
            synced.append(time.monotonic())
        if trace is not None and trace.t_a is not None and trace.path is None:
            traced_steps += chunk
    if trace is not None and trace.path is None:
        if trace.t_a is None:
            raise RuntimeError("the window closed before the trace began")
        trace.stop()
    after = _applied(system)  # waits for the last chunk
    run.t_close = t_close = time.monotonic()

    from benchmark.harness import memory_peak_bytes
    run.memory_peak = memory_peak_bytes(run.devices)
    tells = after - before
    run.attempted, run.failed = tells, 0
    run.end_to_end["tells_per_s"] = tells / (t_close - t_open)
    # as drivers/bank.py: the longest and the median wait between two chunks
    # tell a run in which the host stalled from one with a slow device
    waits = np.diff(synced) if len(synced) > 2 else np.zeros(1)
    run.notes["chunk_wait_s"] = {"median": round(float(np.median(waits)), 4),
                                 "max": round(float(waits.max()), 4),
                                 "over_twice_median": int(
                                     (waits > 2 * np.median(waits)).sum())}

    # what the window left, and one more chunk through the same executable
    got["close"] = _reading(system)
    system.run(chunk)
    got["after"] = _reading(system, with_tellers=True)
    last = got["after"]["counters"]
    run.notes["spill"] = {"spilled": last["spilled"],
                          "high_water": last["spill_high_water"],
                          "a_step": round(last["spilled"] / max(
                              got["after"]["steps"], 1), 1)}
    run.notes["exchange"] = {"pair_cap": int(system.pair_cap),
                             "exchange_high_water":
                                 last["exchange_high_water"],
                             "dropped": last["exchange_dropped"]}
    run.obs.update(steps_in_trace=traced_steps, steps=got["close"]["steps"])
    del system
    t0 = time.monotonic()
    run.compared = reference.judge(tellers, traffic, dep, got, conf["limits"])
    run.notes["replay_s"] = round(time.monotonic() - t0, 3)
    if run.faults.get("controls"):  # benchmark/tools/control_xbank.py asks
        from benchmark.reference import bank_sharded_controls
        run.controls = bank_sharded_controls.judge_controls(
            tellers, traffic, dep, got, conf["limits"])
    run.compared["compiles_in_window"] = {
        "value": run.compiles.between(t_open, t_close),
        "limit": conf["limits"]["compiles_in_window"]}
