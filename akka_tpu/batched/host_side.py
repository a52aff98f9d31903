"""The host side of a step driver, measured from inside the program.

`BatchedSystem` and `ShardedBatchedSystem` build, dispatch, wait and read
through the ONE bracket kept here, so both leave the same spans in the
process's span log (`event/flight_recorder.py`: `span_log()`,
`compile_log()`), in a profiler trace while a session is open, and in the
flight recorder where one is configured:

    akka.setup.build        the constructor, to the carry standing on the
                            device (stats `actors`, `inbox_rows`, `devices`)
    akka.setup.warmup       `warmup()`
    akka.device.run[n]      one dispatch of n steps (`akka.device.step`: of
                            one); stats `step0`, `steps`, `starved`, and in
                            the log `compiled`
    akka.device.wait        `block_until_ready()`
    akka.device.read[what]  a host read that syncs: `state`, `metrics`,
                            `spill`, `routers`

`n_steps` is a static argument of both step programs, so a `run(n)` with an
`n` not seen before is a NEW program: its dispatch reads `compiled > 0`,
`compile_log()` has its row with `steps` n, and a configured flight recorder
gets `device_compile`. docs/OBSERVABILITY.md section 7.
"""

from __future__ import annotations

import functools
import math
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, Optional

import jax
import numpy as np

from ..event.flight_recorder import listen_for_compiles, trace_span


def build_span(init):
    """Bracket a step driver's `__init__` in `akka.setup.build`: from entry
    to the carry (and the topology's tables) standing on the device. The
    stats are the built system's, so they are in the log's record."""

    @functools.wraps(init)
    def build(self, *args, **kwargs):
        with trace_span("akka.setup.build") as span:
            init(self, *args, **kwargs)
            # the topology's tables ride beside the carry where there are any
            jax.block_until_ready((self._carry(),
                                   getattr(self, "_topo_arrays", ())))
            span.stats.update(actors=int(self.capacity),
                              inbox_rows=int(self.inbox_dst.shape[0]),
                              devices=len(self.step_count.sharding.device_set))

    return build


class HostSide:
    """One step driver's bracket and its counters (`host_stats()`).
    `system` is the flight recorder's name for the driver: `batched`,
    `sharded`."""

    def __init__(self, system: str):
        listen_for_compiles()
        self.system = system
        self.dispatches = 0
        self.starved = 0
        self.compiles = 0
        self.compile_s = 0.0
        self.last_compile: Optional[Dict[str, Any]] = None
        self._dispatch_ns: deque = deque(maxlen=4096)
        # a registry pulls host_stats() at every scrape: sort the window
        # again only after a dispatch (`dispatches` is the token)
        self._sorted = (-1, [])

    @contextmanager
    def dispatch(self, name: str, driver, n_steps: int):
        """The one span of a dispatch: the body enqueues `n_steps` steps of
        `driver`, in the caller's own frame (the step program's source
        locations hold the stack it was traced under). `starved`: the
        previous dispatch's non-donated output was ready when this one
        began, so the device had nothing queued and waited for the host."""
        step0 = driver._host_step
        starved = bool(driver.step_count.is_ready())
        with trace_span(name, step0=step0, steps=n_steps,
                        starved=int(starved)) as span:
            yield span
        self.dispatches += 1
        self.starved += starved
        self._dispatch_ns.append(span.t1 - span.t0)
        self._count_compiles(span, step0)
        fr = driver.flight_recorder
        if fr is not None:
            # seconds of the DISPATCH (the launch is asynchronous; the
            # device may still be executing): a slow one is a compile or a
            # host stall in a post-mortem flight
            fr.device_step(self.system, n_steps, span.seconds)
            if span.compiled:
                fr.device_compile(self.system, span.seconds)

    @contextmanager
    def warmup(self, driver):
        with trace_span("akka.setup.warmup") as span:
            yield span
        self._count_compiles(span, driver._host_step)
        if driver.flight_recorder is not None:
            driver.flight_recorder.device_compile(self.system, span.seconds)

    def _count_compiles(self, span, step0: int) -> None:
        if span.compiled:
            self.compiles += span.compiled
            self.compile_s += span.compile_s
            self.last_compile = {"program": span.program, "step": step0}

    @staticmethod
    def wait(handle) -> None:
        """Sync through a host read of a non-donated output: on some
        platforms donated / aliased buffers report ready before the program
        has finished."""
        with trace_span("akka.device.wait"):
            np.asarray(jax.device_get(handle))

    @staticmethod
    def read(what: str) -> trace_span:
        return trace_span(f"akka.device.read[{what}]")

    def host_stats(self) -> Dict[str, Any]:
        """The operator's view of this driver's host side: `dispatches`,
        `dispatch_us_p50` / `_p99` over the last 4,096 of them (a dispatch
        is the asynchronous launch, not the device's execution), `starved`
        (dispatches that found the device with nothing queued), `compiles`
        and `compile_s` (backend compilations or cache loads inside this
        driver's dispatches and warm-up) and `last_compile` (program and
        the step it ran at; None before the first)."""
        seen, d = self._sorted
        if seen != self.dispatches:
            d = sorted(self._dispatch_ns)
            self._sorted = (self.dispatches, d)

        def pct(q: float) -> float:
            # nearest rank, ceil(q * n) counted from 1: p50 of [a, b] is a
            if not d:
                return 0.0
            return round(d[max(math.ceil(q * len(d)) - 1, 0)] / 1e3, 1)

        return {"dispatches": self.dispatches,
                "dispatch_us_p50": pct(0.50), "dispatch_us_p99": pct(0.99),
                "starved": self.starved, "compiles": self.compiles,
                "compile_s": self.compile_s,
                "last_compile": self.last_compile}
