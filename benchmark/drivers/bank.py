"""Driver of the bank cell: the deployment built by
`akka_tpu.models.baseline_benches.build_bank`, stepped by `run(k)` in chunks.

The configuration names the builder and its arguments; the traffic file holds
the constants of the tellers' rule and how long a chunk is. The tellers
(`home`, `stride`, `mask`, `c`) are drawn from the seed by the reference and
handed to the builder as the state the tellers are spawned with. Closed and
device-paced: every teller tells one command, every step, for the whole
window. Tells are counted by the accounts' own `applied` columns, read before
and after the window.

The system is read three times: after the warm chunks (the window then
opens), when the window has closed, and after one more chunk through the
same executable. The reference replays the warm steps from the seed and the
last chunk from what the window left, after the window, so neither the
window nor the set-up waits for it; across the window the system is held to
its own counters (benchmark/reference/bank.py::judge)."""

from __future__ import annotations

import time

import numpy as np

from benchmark.reference import bank as reference

RULE = ("period", "levels")  # constants both files state
SHAPE = ("mailbox_slots", "spill_capacity", "period", "levels")


def _build(run, tellers):
    from akka_tpu.models import baseline_benches as bb

    conf = run.config
    for key in RULE:
        if conf[key] != run.traffic[key]:
            raise ValueError(f"{key}: the configuration states {conf[key]}, "
                             f"the traffic tells {run.traffic[key]}")
    args = dict(conf["builder_args"])
    for key in SHAPE:
        if args[key] != conf[key]:
            raise ValueError(f"{key}: the builder's differs from the "
                             f"configuration's")
    system = getattr(bb, conf["builder"])(tellers=tellers, **args)
    rows = int(system.inbox_dst.shape[0])
    if rows != conf["inbox_rows"]:
        raise ValueError(f"inbox_rows: the configuration states "
                         f"{conf['inbox_rows']}, the builder lays out {rows}")
    return system


def _applied(system, n_accounts: int) -> int:
    """The commands applied so far (waits for every chunk dispatched)."""
    return int(np.asarray(system.read_state("applied"),
                          np.int64)[:n_accounts].sum())


def _reading(system, n_accounts: int, with_tellers: bool = False) -> dict:
    """The accounts, the inbox and the step count, as host copies."""
    snap = {"accounts": {k: system.read_state(k)[:n_accounts]
                         for k in reference.ACCOUNT_COLUMNS},
            "inbox_dst": np.asarray(system.inbox_dst),
            "inbox_type": np.asarray(system.inbox_type),
            "inbox_payload": np.asarray(system.inbox_payload),
            "inbox_valid": np.asarray(system.inbox_valid),
            "steps": int(np.asarray(system.step_count))}
    if with_tellers:
        snap["tellers"] = {k: system.read_state(k)[n_accounts:]
                           for k in reference.TELLER_COLUMNS}
    return snap


def run(run) -> None:
    conf, traffic = run.config, run.traffic
    n_tellers, n_accounts = int(conf["tellers"]), int(conf["accounts"])
    slots = int(conf["mailbox_slots"])
    t0 = time.monotonic()
    tellers = reference.seed_tellers(n_tellers, n_accounts, run.seed, traffic)
    system = _build(run, tellers)
    run.notes["build_s"] = round(time.monotonic() - t0, 3)

    if "bank_step" in run.faults:  # tests plant a fault under the timed path
        run.faults["bank_step"](system)

    chunk = int(traffic["chunk_steps"])
    t0 = time.monotonic()
    for _ in range(int(traffic["warm_chunks"])):
        system.run(chunk)
        system.block_until_ready()
    got = {"open": _reading(system, n_accounts)}
    before = int(np.asarray(got["open"]["accounts"]["applied"],
                            np.int64).sum())
    run.notes["warm_s"] = round(time.monotonic() - t0, 3)

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
    after = _applied(system, n_accounts)  # waits for the last chunk
    run.t_close = t_close = time.monotonic()

    from benchmark.harness import memory_peak_bytes
    run.memory_peak = memory_peak_bytes(run.devices)
    tells = after - before
    run.attempted, run.failed = tells, 0
    run.end_to_end["tells_per_s"] = tells / (t_close - t_open)
    # a window is device-paced while the host comes back inside a chunk's
    # time: the longest and the median wait between two chunks tell a run
    # in which the host stalled from one in which the device was slow
    waits = np.diff(synced) if len(synced) > 2 else np.zeros(1)
    run.notes["chunk_wait_s"] = {"median": round(float(np.median(waits)), 4),
                                 "max": round(float(waits.max()), 4),
                                 "over_twice_median": int(
                                     (waits > 2 * np.median(waits)).sum())}

    # what the window left, and one more chunk through the same executable
    got["close"] = _reading(system, n_accounts)
    system.run(chunk)
    got["after"] = _reading(system, n_accounts, with_tellers=True)
    got["dropped"] = int(system.dropped_messages + system.mailbox_overflow)
    spilled, high_water = system.read_spill()
    steps = got["close"]["steps"]
    run.notes["spill"] = {"spilled": spilled, "high_water": high_water,
                          "a_step": round(spilled / max(
                              got["after"]["steps"], 1), 1)}
    run.obs.update(steps_in_trace=traced_steps, steps=steps)
    del system
    t0 = time.monotonic()
    run.compared = reference.judge(tellers, traffic, n_accounts, slots, got,
                                   conf["limits"])
    run.notes["replay_s"] = round(time.monotonic() - t0, 3)
    if run.faults.get("controls"):  # benchmark/tools/control_bank.py asks
        from benchmark.reference import bank_controls
        run.controls = bank_controls.judge_controls(
            tellers, traffic, n_accounts, slots, conf["spill_capacity"], got,
            conf["limits"])
    run.compared["compiles_in_window"] = {
        "value": run.compiles.between(t_open, t_close),
        "limit": conf["limits"]["compiles_in_window"]}
