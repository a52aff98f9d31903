"""Driver of the device-plane cells: a ring built by
`akka_tpu.models.baseline_benches`, stepped by `run(k)` in chunks.

The configuration names the builder and its arguments; the traffic file says
how the mailboxes are seeded and how long a chunk is. The window drives the
system's own `run` + `block_until_ready`; tells are counted by the device's
`received` counters, read before and after the window."""

from __future__ import annotations

import time

import numpy as np

from benchmark.reference import ring as reference


def _build(run):
    from akka_tpu.models import baseline_benches as bb

    conf = run.config
    args = dict(conf["builder_args"])
    if conf.get("n_devices_arg"):
        args[conf["n_devices_arg"]] = len(run.devices)
    system = getattr(bb, conf["builder"])(**args)
    bb.seed_ring_full(system)  # one token in every mailbox, payload 1.0
    return system


def _seed_payloads(system, table: np.ndarray):
    """Give each seeded token its payload from the seed: the token waiting
    in actor d's mailbox gets table[d]. One jitted call on the device,
    keeping the inbox's sharding."""
    import jax
    import jax.numpy as jnp

    def place(dst, valid, payload, tab):
        got = tab[jnp.clip(dst, 0, tab.shape[0] - 1)]
        return jnp.where(valid[:, None], got, payload)

    # a mesh's inbox keeps its sharding; a single device's stays as the
    # system made it (uncommitted), or its step program would compile twice
    sharding = system.inbox_payload.sharding
    spread = len(sharding.device_set) > 1
    fn = jax.jit(place, out_shardings=sharding) if spread else jax.jit(place)
    system.inbox_payload = fn(system.inbox_dst, system.inbox_valid,
                              system.inbox_payload, jnp.asarray(table))


def _dropped(system) -> int:
    total = 0
    for name in ("total_dropped", "dropped_messages", "mailbox_overflow"):
        if hasattr(system, name):
            total += int(getattr(system, name))
    return total


def _received_sum(system) -> int:
    return int(np.asarray(system.read_state("received"), np.int64).sum())


def run(run) -> None:
    conf, traffic = run.config, run.traffic
    t0 = time.monotonic()
    system = _build(run)
    n = system.capacity
    stride = conf["stride"] if isinstance(conf["stride"], int) else \
        n // len(run.devices)
    width = system.payload_width
    table = reference.seed_payload(n, width, run.seed, traffic)
    _seed_payloads(system, table)
    run.notes["build_s"] = round(time.monotonic() - t0, 3)

    if "ring_step" in run.faults:  # tests plant a fault under the timed path
        run.faults["ring_step"](system)

    chunk = int(traffic["chunk_steps"])
    steps = 0
    t0 = time.monotonic()
    for _ in range(int(traffic["warm_chunks"])):
        system.run(chunk)
        system.block_until_ready()
        steps += chunk
    before = _received_sum(system)
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
        steps += chunk
        if trace is not None and trace.t_a is not None and trace.path is None:
            traced_steps += chunk
    if trace is not None and trace.path is None:
        if trace.t_a is None:
            raise RuntimeError("the window closed before the trace began")
        trace.stop()
    after = _received_sum(system)  # waits for the last chunk
    run.t_close = t_close = time.monotonic()

    from benchmark.harness import memory_peak_bytes
    run.memory_peak = memory_peak_bytes(run.devices)
    tells = after - before
    run.attempted, run.failed = tells, 0
    run.end_to_end["tells_per_s"] = tells / (t_close - t_open)
    run.obs.update(steps_in_trace=traced_steps, steps=steps)

    # what the timed path left behind, against the reference
    got = {"received": system.read_state("received"),
           "inbox_dst": np.asarray(system.inbox_dst),
           "inbox_payload": np.asarray(system.inbox_payload),
           "inbox_valid": np.asarray(system.inbox_valid),
           "dropped": _dropped(system)}
    del system
    run.compared = reference.judge(n, stride, table, steps, got,
                                   conf["limits"])
    if run.faults.get("controls"):  # benchmark/tools/control.py asks
        from benchmark.reference import controls
        run.controls = controls.judge_ring_controls(n, stride, table, steps,
                                                    conf["limits"])
    run.compared["compiles_in_window"] = {
        "value": run.compiles.between(t_open, t_close),
        "limit": conf["limits"]["compiles_in_window"]}
