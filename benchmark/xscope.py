"""Which layer of the step program an operation of the device trace belongs
to: the `jax.named_scope` path that JAX wrote into the operation's metadata.

The program names its layers while it traces (`akka_tpu/batched/step.py`:
`akka.deliver`, `akka.behavior`, `akka.exchange`, ...; blocks nest as
`akka.deliver.merge_sort`). XLA keeps that path as each HLO instruction's
`metadata.op_name`, e.g.

    jit(_run_impl)/while/body/closed_call/akka.deliver/akka.deliver.merge_sort/sort

and the profiler writes every program that ran, optimized, into the
`.xplane.pb`: the `/host:metadata` plane has one event metadata per program,
named `jit__run_impl(<program id>)`, whose `Hlo Proto` stat is the serialized
`HloProto`. (A TPU's device plane repeats the path per operation as a `tf_op`
stat; the host's copy is read because the CPU backend writes it too, and the
tests run every metric there.) `jax.profiler.ProfileData`, which
`benchmark/xplane.py` reads with, shows an event's own stats but not its
metadata's, so this file reads the protobuf's wire format itself: a
varint/length-delimited walk over the few fields needed, standard library
only. Field numbers: XSpace.planes=1; XPlane name=2, event_metadata=4 (map:
key=1, value=2), stat_metadata=5 (map); XEventMetadata name=2, stats=5;
XStatMetadata id=1, name=2; XStat metadata_id=1, bytes_value=6
(tsl/profiler/protobuf/xplane.proto). HloProto.hlo_module=1;
HloModuleProto.computations=3; HloComputationProto.instructions=2;
HloInstructionProto name=1, metadata=7; OpMetadata.op_name=2
(xla/service/hlo.proto, xla/xla_data.proto).

An operation is keyed `<hlo module>/<instruction>`, as `xplane.load` names
the device's operations. A fusion carries the path of one of its members;
instructions XLA makes from an outlined callee (`cumsum` becomes
`reduce-window`s) carry no path."""

from __future__ import annotations

import glob
import os
import tempfile

from benchmark import xplane
from benchmark.harness import BenchError

UNSCOPED = "unscoped"
SCOPE_PREFIX = "akka."


# ------------------------------------------------------- the wire format
def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of one message: an int for a varint, bytes for
    a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise BenchError(f"xplane.pb: wire type {wire} at byte {i}")
        yield key >> 3, value


def _first(buf: bytes, field: int, default=b""):
    return next((v for f, v in _fields(buf) if f == field), default)


def _map_entries(plane: bytes, field: int):
    """The values of a `map<int64, Message>` field of a plane."""
    for f, entry in _fields(plane):
        if f == field:
            yield _first(entry, 2)


def _programs(space: bytes):
    """(module name, serialized HloProto) of every program the trace's
    `/host:metadata` plane holds."""
    for f, plane in _fields(space):
        if f != 1 or _first(plane, 2) != b"/host:metadata":
            continue
        hlo_stat = next((_first(m, 1) for m in _map_entries(plane, 5)
                         if _first(m, 2) == b"Hlo Proto"), None)
        for meta in _map_entries(plane, 4):
            module = xplane.module_name(_first(meta, 2).decode())
            for g, stat in _fields(meta):
                if g == 5 and _first(stat, 1) == hlo_stat:
                    yield module, _first(stat, 6)


def read_scopes(path: str) -> dict:
    """`<module>/<instruction>` -> scope path, for every instruction with an
    `op_name` of every program in the `.xplane.pb` at `path`. Two programs
    that ran under one module name and disagree about an instruction's path
    leave it without one."""
    with open(path, "rb") as f:
        space = f.read()
    out, clash = {}, set()
    for module, hlo in _programs(space):
        for f, computation in _fields(_first(hlo, 1)):
            if f != 3:
                continue
            for g, instr in _fields(computation):
                if g != 2:
                    continue
                scope = _first(_first(instr, 7), 2).decode("utf-8", "replace")
                if not scope:
                    continue
                key = f"{module}/{_first(instr, 1).decode()}"
                if out.setdefault(key, scope) != scope:
                    clash.add(key)
    for key in clash:
        del out[key]
    return out


# ----------------------------------------------------------- the grouping
def layer_of(scope: str | None):
    """(layer, deepest block) of a scope path: its first and its last
    `akka.` component; (`unscoped`, `unscoped`) where it has none."""
    named = [c for c in (scope or "").split("/")
             if c.startswith(SCOPE_PREFIX)]
    return (named[0], named[-1]) if named else (UNSCOPED, UNSCOPED)


def scope_sums(trace, scopes: dict, a: float, b: float,
               device: int = 0) -> dict:
    """(layer, block) -> {`<module>/<op>`: seconds of SELF time inside
    [a, b) on one device}: `xplane.op_sums`, grouped. Every operation is in
    exactly one group, so the groups add up to the busy time."""
    out = {}
    for name, seconds in xplane.op_sums(trace, a, b, device).items():
        out.setdefault(layer_of(scopes.get(name)), {})[name] = seconds
    return out


# ------------------------------------------------------ finding the file
def find_trace_file(mark_a: float, root: str | None = None) -> str:
    """The `.xplane.pb` of this run. The harness keeps it under
    `<tmp>/bench_*/trace/` until the readers are done, and hands them the
    loaded trace but no path: so it is found by content, as the one file
    whose `bench.mark.a` starts at `mark_a`."""
    root = root or tempfile.gettempdir()
    found = [p for p in sorted(glob.glob(os.path.join(
        root, "bench_*", "trace", "plugins", "profile", "*", "*.xplane.pb")))
        if xplane.find_marks(xplane.load(p)).get("a") == mark_a]
    if len(found) != 1:
        raise BenchError(
            f"{len(found)} trace files under {root}/bench_*/trace carry "
            f"this run's mark, not 1: {found}")
    return found[0]
