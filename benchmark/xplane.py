"""From a profiler trace (`.xplane.pb`) to numbers: device busy time, idle
share, per-operation sums named `<hlo module>/<op>`, and the idle gaps
attributed to what the host was doing.

Read with `jax.profiler.ProfileData`, nothing else. A TPU's device plane
(`/device:TPU:n`) has a line of HLO operations (`XLA Ops`) and a line of
program executions (`XLA Modules`, one event per run of a compiled program,
named `jit_<fn>(<fingerprint>)`). An operation belongs to the program whose
execution contains its start. Host planes (`/host:CPU`) carry one line per
thread with TraceMe events (`PjitFunction(...)`, the program's own
`akka.device.run[n]` annotations, ours). All lines share one clock, in
nanoseconds. A trace without a device plane loads with no device, and the
harness refuses it: nothing here lets host operations stand in for a chip's.
tests/benchmark reduces a small recorded trace with this file."""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the device's and the host's lines agree to about a millisecond (a program's
# execution can read as starting before the call that launched it), so a
# shorter gap cannot be given to a host event: it is summed as "short_gaps"
SHORT_GAP_NS = 1_000_000


@dataclass
class Event:
    name: str
    start: float  # ns
    end: float


@dataclass
class DeviceLine:
    device: str
    ops: list = field(default_factory=list)      # Event, name "<module>/<op>"
    modules: list = field(default_factory=list)  # Event, name "<module>"


@dataclass
class Trace:
    devices: list  # DeviceLine
    host: list     # Event, every thread's


def module_name(raw: str) -> str:
    """`jit_multi_step(7329087)` -> `jit_multi_step`."""
    return re.sub(r"\(\d+\)$", "", raw)


def op_name(raw: str) -> str:
    """A TPU trace names an operation by its whole HLO line,
    `%fusion.53 = f32[...] fusion(...)`: keep `fusion.53`."""
    m = re.match(r"%(\S+) = ", raw)
    return m.group(1) if m else raw


def load(path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dl = DeviceLine(plane.name)
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    dl.modules = [Event(module_name(e.name), e.start_ns,
                                        e.start_ns + e.duration_ns)
                                  for e in line.events]
                elif line.name == OPS_LINE:
                    dl.ops = [Event(op_name(e.name), e.start_ns,
                                    e.start_ns + e.duration_ns)
                              for e in line.events]
            if not dl.ops and not dl.modules:
                continue
            dl.modules.sort(key=lambda e: e.start)
            starts = [m.start for m in dl.modules]
            for op in dl.ops:
                i = bisect.bisect_right(starts, op.start) - 1
                mod = dl.modules[i].name if i >= 0 and \
                    op.start < dl.modules[i].end else "no_module"
                op.name = f"{mod}/{op.name}"
            devices.append(dl)
        elif plane.name.startswith("/host:CPU"):
            host.extend(Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for line in plane.lines for e in line.events
                        if e.duration_ns > 0)
    return Trace(devices, host)


def clip(events, a: float, b: float):
    """The parts of `events` inside [a, b)."""
    return [Event(e.name, max(e.start, a), min(e.end, b))
            for e in events if e.end > a and e.start < b]


def union(events) -> list:
    """Merged, sorted (start, end) intervals covered by `events`."""
    out = []
    for s, e in sorted((e.start, e.end) for e in events):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def busy_ns(events) -> float:
    return sum(e - s for s, e in union(events))


def busy_events(dl: DeviceLine) -> list:
    """What counts as the device being busy: its operations; where a trace
    has no operation line, its program executions."""
    return dl.ops or dl.modules


def device_busy(trace: Trace, a: float, b: float) -> list:
    """Per device: seconds in which an operation ran inside [a, b)."""
    return [busy_ns(clip(busy_events(dl), a, b)) / 1e9 for dl in trace.devices]


def idle_share(trace: Trace, a: float, b: float) -> float:
    """1 - busy / window on the fullest device, in percent."""
    return 100.0 * (1.0 - max(device_busy(trace, a, b)) / ((b - a) / 1e9))


def op_sums(trace: Trace, a: float, b: float, device: int = 0) -> dict:
    """`<module>/<op>` -> seconds of SELF time inside [a, b) on one device.
    Operations nest (a `while` holds its body's operations), so an
    operation's own time is its span less what its children cover; summed
    over all operations that is the busy time, counted once."""
    out = {}
    stack = []  # [event, covered_by_children_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][0].end <= upto:
            e, covered = stack.pop()
            own = (e.end - e.start) - covered
            out[e.name] = out.get(e.name, 0.0) + max(own, 0.0) / 1e9
            if stack:
                stack[-1][1] += e.end - e.start

    for e in sorted(clip(trace.devices[device].ops, a, b),
                    key=lambda e: (e.start, -e.end)):
        close(e.start)
        if stack and e.end > stack[-1][0].end:  # overlaps, not nested: clip
            e = Event(e.name, e.start, stack[-1][0].end)
        stack.append([e, 0.0])
    close(float("inf"))
    return out


def module_sums(trace: Trace, a: float, b: float, device: int = 0) -> dict:
    out = {}
    for e in clip(trace.devices[device].modules, a, b):
        out[e.name] = out.get(e.name, 0.0) + (e.end - e.start) / 1e9
    return out


def fullest(trace: Trace, a: float, b: float) -> int:
    busy = device_busy(trace, a, b)
    return busy.index(max(busy))


def gaps(trace: Trace, a: float, b: float, device: int = 0) -> list:
    """Idle (start, end) intervals of one device inside [a, b)."""
    out, at = [], a
    for s, e in union(clip(busy_events(trace.devices[device]), a, b)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if b > at:
        out.append((at, b))
    return out


def attribute_gaps(trace: Trace, a: float, b: float, device: int = 0,
                   skip_prefix=("bench.",)) -> dict:
    """name -> idle seconds. Each idle gap of `short_gap` or more goes to the
    shortest host event that covers at least half of it, else to the one that
    overlaps it most; gaps that meet no host event are `unattributed`."""
    host = [e for e in trace.host if not e.name.startswith(skip_prefix)]
    host.sort(key=lambda e: e.start)
    starts = [e.start for e in host]
    longest = max((e.end - e.start for e in host), default=0.0)
    out = {}

    def add(name, ns):
        out[name] = out.get(name, 0.0) + ns / 1e9

    for s, e in gaps(trace, a, b, device):
        if e - s < SHORT_GAP_NS:
            add("short_gaps", e - s)
            continue
        lo = bisect.bisect_left(starts, s - longest)
        hi = bisect.bisect_left(starts, e)
        best = None  # (covers_half, -duration | overlap, name)
        for h in host[lo:hi]:
            ov = min(h.end, e) - max(h.start, s)
            if ov <= 0:
                continue
            key = (1, -(h.end - h.start)) if ov >= 0.5 * (e - s) else (0, ov)
            if best is None or key > best[0]:
                best = (key, h.name)
        add(best[1] if best else "unattributed", e - s)
    return out


def top(d: dict, k: int = 10) -> list:
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def find_marks(trace: Trace, prefix: str = "bench.mark.") -> dict:
    """Host annotations `bench.mark.<label>` -> their start, in trace ns."""
    return {e.name[len(prefix):]: e.start for e in trace.host
            if e.name.startswith(prefix)}
