"""Driver of the router pool cell: the deployment built by
`akka_tpu.models.baseline_benches.build_router_pool`, stepped by `run(k)` in
chunks.

The configuration names the builder and its arguments; the traffic file holds
the constants of the producers' tell rule and how long a chunk is. The
producers (the router's ref, the mask of steps on which each tells, its job)
are drawn from the seed by the reference and handed to the builder as the
state the producers are spawned with. Closed and device-paced: whoever's mask
says so tells the one router ref, every step, for the whole window. Tells
are counted by the routees' own `hits` columns, read before and after the
window."""

from __future__ import annotations

import time

import numpy as np

from benchmark.reference import router as reference

RULE = ("mask_period", "tell_one_in")  # constants both files state


def _build(run, producers):
    from akka_tpu.models import baseline_benches as bb

    conf = run.config
    for key in RULE:
        if conf[key] != run.traffic[key]:
            raise ValueError(f"{key}: the configuration states {conf[key]}, "
                             f"the traffic tells {run.traffic[key]}")
    args = dict(conf["builder_args"])
    if args["mask_period"] != conf["mask_period"]:
        raise ValueError("mask_period: the builder's differs from the "
                         "configuration's")
    system = getattr(bb, conf["builder"])(producers=producers, **args)
    rows = int(system.inbox_dst.shape[0])
    if rows != conf["inbox_rows"]:
        raise ValueError(f"inbox_rows: the configuration states "
                         f"{conf['inbox_rows']}, the builder lays out {rows}")
    return system


def _dropped(system) -> int:
    return sum(int(getattr(system, name)) for name in
               ("total_dropped", "dropped_messages", "mailbox_overflow")
               if hasattr(system, name))


def _hits(system, n_routees: int) -> np.ndarray:
    """The routees' counts (waits for every chunk dispatched)."""
    return np.asarray(system.read_state("hits"), np.int64)[:n_routees]


def run(run) -> None:
    conf, traffic = run.config, run.traffic
    n_prod, n_routees = int(conf["producers"]), int(conf["routees"])
    logic = conf["builder_args"]["logic"]
    router = n_routees  # the router's row: routees first, then the router
    t0 = time.monotonic()
    producers = reference.seed_producers(n_prod, router, run.seed, traffic)
    system = _build(run, producers)
    run.notes["build_s"] = round(time.monotonic() - t0, 3)

    if "router_step" in run.faults:  # tests plant a fault under the timed path
        run.faults["router_step"](system)

    chunk = int(traffic["chunk_steps"])
    t0 = time.monotonic()
    for _ in range(int(traffic["warm_chunks"])):
        system.run(chunk)
        system.block_until_ready()
    before = _hits(system, n_routees)
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
    after = _hits(system, n_routees)  # waits for the last chunk
    run.t_close = t_close = time.monotonic()

    from benchmark.harness import memory_peak_bytes
    run.memory_peak = memory_peak_bytes(run.devices)
    tells = int(((after - before) % (1 << 32)).sum())  # int32 columns wrap
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
    # the rule's step counter is the system's own, not a count of chunks
    steps = int(np.asarray(system.step_count))
    run.obs.update(steps_in_trace=traced_steps, steps=steps)

    # what the timed path left behind, against the reference
    rows = slice(router + 1, router + 1 + n_prod)
    got = {"routees": {k: system.read_state(k)[:n_routees]
                       for k in reference.ROUTEE_COLUMNS},
           "router": {k: int(system.read_state(k)[router])
                      for k in reference.ROUTER_COLUMNS},
           "producers": {k: system.read_state(k)[rows]
                         for k in reference.PRODUCER_COLUMNS},
           "inbox_dst": np.asarray(system.inbox_dst),
           "inbox_payload": np.asarray(system.inbox_payload),
           "inbox_valid": np.asarray(system.inbox_valid),
           "dropped": _dropped(system)}
    del system
    run.compared = reference.judge(steps, producers, traffic, n_routees,
                                   logic, got, conf["limits"])
    if run.faults.get("controls"):  # benchmark/tools/control_router.py asks
        from benchmark.reference import router_controls
        run.controls = router_controls.judge_controls(
            steps, producers, traffic, n_routees, logic, conf["limits"])
    run.compared["compiles_in_window"] = {
        "value": run.compiles.between(t_open, t_close),
        "limit": conf["limits"]["compiles_in_window"]}
