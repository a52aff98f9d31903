"""Driver of the fan-in aggregator cell: the deployment built by
`akka_tpu.models.baseline_benches.build_fan_in`, stepped by `run(k)` in
chunks.

The configuration names the builder and its arguments; the traffic file holds
the constants of the leaves' message rule and how long a chunk is. The leaves
(the collector each tells, its readings, its alarm) are drawn from the seed
by the reference and handed to the builder as the state the leaves are
spawned with. Closed and device-paced: every leaf tells every step, for the
whole window. Tells are counted by the collectors' own `msgs` columns, read
before and after the window."""

from __future__ import annotations

import time

import numpy as np

from benchmark.reference import fanin as reference

RULE = ("reading_levels", "alarm_period")  # constants both files state


def _build(run, leaves):
    from akka_tpu.models import baseline_benches as bb

    conf = run.config
    args = dict(conf["builder_args"])
    for key in RULE:
        if args[key] != run.traffic[key]:
            raise ValueError(f"{key}: the configuration builds for "
                             f"{args[key]}, the traffic tells {run.traffic[key]}")
    return getattr(bb, conf["builder"])(leaves=leaves, **args)


def _dropped(system) -> int:
    return sum(int(getattr(system, name)) for name in
               ("total_dropped", "dropped_messages", "mailbox_overflow")
               if hasattr(system, name))


def _msgs(system, n_collectors: int) -> np.ndarray:
    """The collectors' counts (waits for every chunk dispatched)."""
    return np.asarray(system.read_state("msgs"), np.int64)[:n_collectors]


def run(run) -> None:
    conf, traffic = run.config, run.traffic
    n_leaves, n_coll = int(conf["leaves"]), int(conf["collectors"])
    t0 = time.monotonic()
    leaves = reference.seed_leaves(n_leaves, n_coll, run.seed, traffic)
    system = _build(run, leaves)
    run.notes["build_s"] = round(time.monotonic() - t0, 3)

    if "fanin_step" in run.faults:  # tests plant a fault under the timed path
        run.faults["fanin_step"](system)

    chunk = int(traffic["chunk_steps"])
    t0 = time.monotonic()
    for _ in range(int(traffic["warm_chunks"])):
        system.run(chunk)
        system.block_until_ready()
    before = _msgs(system, n_coll)
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
        if trace is not None and trace.t_a is not None and trace.path is None:
            traced_steps += chunk
    if trace is not None and trace.path is None:
        if trace.t_a is None:
            raise RuntimeError("the window closed before the trace began")
        trace.stop()
    after = _msgs(system, n_coll)  # waits for the last chunk
    run.t_close = t_close = time.monotonic()

    from benchmark.harness import memory_peak_bytes
    run.memory_peak = memory_peak_bytes(run.devices)
    tells = int(((after - before) % (1 << 32)).sum())  # int32 columns wrap
    run.attempted, run.failed = tells, 0
    run.end_to_end["tells_per_s"] = tells / (t_close - t_open)
    # the rule's step counter is the system's own, not a count of chunks
    steps = int(np.asarray(system.step_count))
    run.obs.update(steps_in_trace=traced_steps, steps=steps)

    # what the timed path left behind, against the reference
    rows = slice(n_coll, n_coll + n_leaves)
    got = {"collectors": {k: system.read_state(k)[:n_coll]
                          for k in reference.COLLECTOR_COLUMNS},
           "leaves": {k: system.read_state(k)[rows]
                      for k in reference.LEAF_COLUMNS},
           "inbox_dst": np.asarray(system.inbox_dst),
           "inbox_payload": np.asarray(system.inbox_payload),
           "inbox_valid": np.asarray(system.inbox_valid),
           "dropped": _dropped(system)}
    del system
    run.compared = reference.judge(steps, leaves, traffic, n_coll, got,
                                   conf["limits"])
    if run.faults.get("controls"):  # benchmark/tools/control.py asks
        from benchmark.reference import fanin_controls
        run.controls = fanin_controls.judge_controls(
            steps, leaves, traffic, n_coll, conf["limits"])
    run.compared["compiles_in_window"] = {
        "value": run.compiles.between(t_open, t_close),
        "limit": conf["limits"]["compiles_in_window"]}
