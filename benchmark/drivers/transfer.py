"""Driver of the bank-transfer cell (Savina's Bank Transaction): the
deployment built by `akka_tpu.models.baseline_benches.build_bank_transfer`,
stepped by `run(k)` in chunks.

As benchmark/drivers/bank.py, whose docstring holds for this file too: the
tellers' keys are drawn from the seed by the reference and handed to the
builder; closed and device-paced, two chunks in flight; the system is read
after the warm chunks, when the window has closed, and after one more chunk
through the same executable, and judged by
benchmark/reference/bank_transfer.py::judge after the window. What differs:
the tells counted are those HANDLED (Credits, Debits and the two Replies of
every transfer: the growth of the accounts' `credits`, `debits`, `replies`
and the tellers' `done`, `mismatch`), a reading holds every stash, and the
system's emitting-path counters (`read_emit_stats`) are read at the opening,
at the close and after the last chunk: `stash_share` for the per-layer
metric of that name, the high waters for the log."""

from __future__ import annotations

import time

import numpy as np

from benchmark.reference import bank_transfer as reference

# the builder's arguments the configuration states beside them
SHAPE = {"n_accounts": "accounts", "n_tellers": "tellers",
         "mailbox_slots": "mailbox_slots", "emit_width": "emit_width",
         "stash_capacity": "stash_capacity",
         "spill_capacity": "spill_capacity"}
HANDLED = (("credits", "debits", "replies"), ("done", "mismatch"))


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
    system = builder(keys=keys, **args)
    rows = int(system.inbox_dst.shape[0])
    if rows != conf["inbox_rows"]:
        raise ValueError(f"inbox_rows: the configuration states "
                         f"{conf['inbox_rows']}, the builder lays out {rows}")
    return system


def _handled(system, n_accounts: int) -> int:
    """The tells handled so far (waits for every chunk dispatched)."""
    accounts, tellers = HANDLED
    return int(sum(np.asarray(system.read_state(k)[:n_accounts], np.int64)
                   .sum() for k in accounts)
               + sum(np.asarray(system.read_state(k)[n_accounts:], np.int64)
                     .sum() for k in tellers))


def _reading(system, n_accounts: int) -> dict:
    """Accounts, stashes, tellers, the inbox and the step count, as host
    copies."""
    return {"accounts": {k: system.read_state(k)[:n_accounts]
                         for k in reference.ACCOUNT_COLUMNS},
            "stash": {k: system.read_state(k)[:n_accounts]
                      for k in ("stash_type", "stash_payload", "stash_head",
                                "stash_size")},
            "tellers": {k: system.read_state(k)[n_accounts:]
                        for k in reference.TELLER_COLUMNS},
            "inbox_dst": np.asarray(system.inbox_dst),
            "inbox_type": np.asarray(system.inbox_type),
            "inbox_payload": np.asarray(system.inbox_payload),
            "inbox_valid": np.asarray(system.inbox_valid),
            "steps": int(np.asarray(system.step_count))}


def run(run) -> None:
    conf, traffic = run.config, run.traffic
    n_tellers, n_accounts = int(conf["tellers"]), int(conf["accounts"])
    t0 = time.monotonic()
    keys = reference.seed_keys(n_tellers, run.seed)
    system = _build(run, keys)
    run.notes["build_s"] = round(time.monotonic() - t0, 3)

    if "transfer_step" in run.faults:  # tests plant a fault under the path
        run.faults["transfer_step"](system)

    chunk = int(traffic["chunk_steps"])
    t0 = time.monotonic()
    for _ in range(int(traffic["warm_chunks"])):
        system.run(chunk)
        system.block_until_ready()
    got = {"open": _reading(system, n_accounts)}
    counters = {"open": system.read_emit_stats()}
    before = _handled(system, n_accounts)
    run.notes["warm_s"] = round(time.monotonic() - t0, 3)

    trace = run.device_trace
    trace_from = float(traffic["trace_after_seconds"])
    trace_for = float(traffic["trace_seconds"])
    traced_steps = 0
    # two chunks in flight, one where traced (drivers/bank.py)
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
    after = _handled(system, n_accounts)  # waits for the last chunk
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

    got["close"] = _reading(system, n_accounts)
    counters["close"] = system.read_emit_stats()
    system.run(chunk)
    got["after"] = _reading(system, n_accounts)
    got["counters"] = counters["after"] = system.read_emit_stats()
    got["dropped"] = int(system.dropped_messages + system.mailbox_overflow)
    spilled, high_water = system.read_spill()
    steps = got["close"]["steps"]
    window_steps = steps - got["open"]["steps"]
    stashed = (counters["close"]["stashed"]
               - counters["open"]["stashed"]) % (1 << 32)
    run.notes["transfer"] = {
        "steps": window_steps, "tells_a_step": round(tells / max(
            window_steps, 1), 1),
        "spilled": spilled, "spill_high_water": high_water,
        **counters["after"]}
    run.obs.update(steps_in_trace=traced_steps, steps=steps,
                   stash_share=100.0 * stashed / tells if tells else None)
    del system
    t0 = time.monotonic()
    rule = reference.Rule(n_accounts, conf["mailbox_slots"],
                          conf["emit_width"], conf["stash_capacity"],
                          conf["spill_capacity"])
    run.compared = reference.judge(keys, rule, got, conf["limits"])
    run.notes["replay_s"] = round(time.monotonic() - t0, 3)
    if run.faults.get("controls"):  # benchmark/tools/control_transfer.py
        from benchmark.reference import bank_transfer_controls
        run.controls = bank_transfer_controls.judge_controls(
            keys, rule, got, conf["limits"])
    run.compared["compiles_in_window"] = {
        "value": run.compiles.between(t_open, t_close),
        "limit": conf["limits"]["compiles_in_window"]}
