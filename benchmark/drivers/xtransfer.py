"""Driver of the sharded bank-transfer cell (Savina's Bank Transaction,
cluster-sharded): the deployment built by
`akka_tpu.models.baseline_benches.build_bank_transfer_sharded` over the
run's chips, stepped by `run(k)` in chunks.

As benchmark/drivers/transfer.py, whose docstring holds for this file too:
the tellers' keys are drawn from the seed by the reference and handed to the
builder; closed and device-paced, two chunks in flight; the tells counted
are those HANDLED; the system is read after the warm chunks, when the window
has closed, and after one more chunk through the same executable, and judged
by benchmark/reference/bank_transfer_sharded.py::judge after the window.
What differs is the cluster, as in benchmark/drivers/xbank.py: the builder
is told the number of chips, a reading holds the accounts and their stashes
at the rows the REFERENCE's placement gives them (never the builder's), the
inbox is a block a chip, and every reading carries the device's counters:
the emitting path's (`read_emit_stats`), the mesh's (`read_mesh_stats`) and
what the exchange and the spill regions lost. `stash_share` for the
per-layer metric of that name is `stashed`' growth over the window's tells."""

from __future__ import annotations

import time

import numpy as np

from benchmark.drivers.transfer import HANDLED
from benchmark.drivers.xbank import _setup_of_the_program
from benchmark.reference import bank_transfer_sharded as reference

# the builder's arguments the configuration states beside them
SHAPE = {"n_accounts": "accounts", "n_tellers": "tellers",
         "n_shards": "logical_shards", "mailbox_slots": "mailbox_slots",
         "emit_width": "emit_width", "stash_capacity": "stash_capacity",
         "spill_capacity": "spill_capacity",
         "remote_capacity_per_pair": "remote_capacity_per_pair"}
STASH = ("stash_type", "stash_payload", "stash_head", "stash_size")


def _build(run, keys):
    from akka_tpu.models import baseline_benches as bb

    conf = run.config
    builder = getattr(bb, conf["builder"], None)
    if builder is None:  # a program from before the configuration
        from benchmark.harness import BenchError
        raise BenchError(f"the program has no builder {conf['builder']!r} "
                         f"(akka_tpu.models.baseline_benches): it cannot "
                         f"run the configuration {conf['name']!r}")
    args = dict(conf["builder_args"])
    for key, stated in SHAPE.items():
        if args[key] != conf[stated]:
            raise ValueError(f"{key}: the builder's differs from the "
                             f"configuration's {stated}")
    args[conf["n_devices_arg"]] = len(run.devices)
    system = builder(keys=keys, **args)
    rows = int(system.inbox_dst.shape[0]) // len(run.devices)
    if rows != conf["inbox_rows_per_chip"]:
        raise ValueError(f"inbox_rows_per_chip: the configuration states "
                         f"{conf['inbox_rows_per_chip']}, the builder lays "
                         f"out {rows}")
    return system


def _handled(system) -> int:
    """The tells handled so far (waits for every chunk dispatched): an
    account's columns hold 0 at a teller's row, and a teller's at an
    account's."""
    return int(sum(np.asarray(system.read_state(k), np.int64).sum()
                   for cols in HANDLED for k in cols))


def _reading(system, dep) -> dict:
    """Accounts and stashes by id and tellers by index, at the rows the
    reference's placement gives, the inbox, the step count and the
    counters, as host copies."""
    return {"accounts": {k: system.read_state(k)[dep.account_row]
                         for k in reference.ACCOUNT_COLUMNS},
            "stash": {k: system.read_state(k)[dep.account_row]
                      for k in STASH},
            "tellers": {k: system.read_state(k)[dep.teller_row]
                        for k in reference.TELLER_COLUMNS},
            "inbox_dst": np.asarray(system.inbox_dst),
            "inbox_type": np.asarray(system.inbox_type),
            "inbox_payload": np.asarray(system.inbox_payload),
            "inbox_valid": np.asarray(system.inbox_valid),
            "steps": int(np.asarray(system.step_count)),
            "counters": {**system.read_emit_stats(),
                         **system.read_mesh_stats(),
                         "mail_dropped": int(system.mailbox_overflow),
                         "exchange_dropped": int(system.total_dropped)}}


def run(run) -> None:
    conf, traffic = run.config, run.traffic
    dep = reference.from_config(conf, len(run.devices))
    t0 = time.monotonic()
    keys = reference.seed_keys(dep.n_tellers, run.seed)
    system = _build(run, keys)
    run.notes["build_s"] = round(time.monotonic() - t0, 3)

    if "xtransfer_step" in run.faults:  # tests plant a fault under the path
        run.faults["xtransfer_step"](system)

    chunk = int(traffic["chunk_steps"])
    t0 = time.monotonic()
    for _ in range(int(traffic["warm_chunks"])):
        system.run(chunk)
        system.block_until_ready()
    got = {"open": _reading(system, dep)}
    before = _handled(system)
    run.notes["warm_s"] = round(time.monotonic() - t0, 3)
    run.notes["setup_program"] = _setup_of_the_program()

    trace = run.device_trace
    trace_from = float(traffic["trace_after_seconds"])
    trace_for = float(traffic["trace_seconds"])
    traced_steps = 0
    # two chunks in flight, one where traced (drivers/xbank.py)
    depth = 1 if trace is not None else 2
    in_flight = []
    synced = []
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
    after = _handled(system)  # waits for the last chunk
    run.t_close = t_close = time.monotonic()

    from benchmark.harness import memory_peak_bytes
    run.memory_peak = memory_peak_bytes(run.devices)
    tells = after - before
    run.attempted, run.failed = tells, 0
    run.end_to_end["tells_per_s"] = tells / (t_close - t_open)
    waits = np.diff(synced) if len(synced) > 2 else np.zeros(1)
    run.notes["chunk_wait_s"] = {"median": round(float(np.median(waits)), 4),
                                 "max": round(float(waits.max()), 4),
                                 "over_twice_median": int(
                                     (waits > 2 * np.median(waits)).sum())}

    got["close"] = _reading(system, dep)
    system.run(chunk)
    got["after"] = _reading(system, dep)
    last = got["after"]["counters"]
    window_steps = got["close"]["steps"] - got["open"]["steps"]
    stashed = (got["close"]["counters"]["stashed"]
               - got["open"]["counters"]["stashed"]) % (1 << 32)
    run.notes["xtransfer"] = {
        "steps": window_steps,
        "tells_a_step": round(tells / max(window_steps, 1), 1),
        "pair_cap": int(system.pair_cap), **last}
    run.obs.update(steps_in_trace=traced_steps,
                   steps=got["close"]["steps"],
                   stash_share=100.0 * stashed / tells if tells else None)
    del system
    t0 = time.monotonic()
    run.compared = reference.judge(keys, dep, got, conf["limits"])
    run.notes["replay_s"] = round(time.monotonic() - t0, 3)
    if run.faults.get("controls"):  # benchmark/tools/control_xtransfer.py
        from benchmark.reference import bank_transfer_sharded_controls
        run.controls = bank_transfer_sharded_controls.judge_controls(
            keys, dep, got, conf["limits"])
    run.compared["compiles_in_window"] = {
        "value": run.compiles.between(t_open, t_close),
        "limit": conf["limits"]["compiles_in_window"]}
