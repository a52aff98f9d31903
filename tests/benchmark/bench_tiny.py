"""A throw-away checkout for the tests: the benchmark's own files linked into
a temporary root, beside a manifest whose cells are the real ones at toy
sizes. Everything a test adds is a new file or a new entry."""

from __future__ import annotations

import copy
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KINDS = ("configs", "traffic", "metrics", "readers", "drivers")

TINY = {
    "actors-1m": {"builder_args": {"n": 1024, "static": False,
                                   "delivery": "auto"}, "actors": 1024},
    "sharded-ring-256x4096": {"builder_args": {"n_shards": 8,
                                               "entities_per_shard": 64},
                              "actors": 512, "shards": 8,
                              "entities_per_shard": 64},
}
TINY_TRAFFIC = {
    "ring-full": {"chunk_steps": 2, "warm_chunks": 1,
                  "trace_after_seconds": 0.05, "trace_seconds": 0.1},
}


def tiny_root(tmp_path) -> str:
    """Build the root; returns its path."""
    root = str(tmp_path / "checkout")
    bench = os.path.join(root, "benchmark")
    for kind in KINDS:
        os.makedirs(os.path.join(bench, kind))
        src = os.path.join(REPO, "benchmark", kind)
        for name in os.listdir(src):
            if name.endswith((".json", ".py")):
                os.symlink(os.path.join(src, name),
                           os.path.join(bench, kind, name))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    for conf in man["configs"]:
        _shrink(root, conf["file"], TINY[conf["name"]])
    for name, change in TINY_TRAFFIC.items():
        _shrink(root, f"benchmark/traffic/{name}.json", change)
    write_manifest(root, man)
    return root


def _shrink(root: str, rel: str, change: dict) -> None:
    path = os.path.join(root, rel)
    with open(path) as f:
        data = json.load(f)
    data = copy.deepcopy(data)
    for k, v in change.items():
        if isinstance(v, dict) and isinstance(data.get(k), dict):
            data[k] = {**data[k], **v}
        else:
            data[k] = v
    os.unlink(path)  # the link, not the file it points at
    with open(path, "w") as f:
        json.dump(data, f)


def write_manifest(root: str, man: dict) -> None:
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)


def add_file(root: str, rel: str, data) -> None:
    """A NEW file under the root (refuses to touch one that is there)."""
    path = os.path.join(root, rel)
    assert not os.path.lexists(path), f"{rel} exists: tests only add files"
    with open(path, "w") as f:
        if isinstance(data, str):
            f.write(data)
        else:
            json.dump(data, f)


def load_cpu_trace_as_device(real_load):
    """A stand-in for `xplane.load`, for the toy runs only: the CPU backend
    writes no device plane, so its operations (host-thread events that carry
    an `hlo_op`) are made into one. The yardstick has no such path: a
    measured run whose trace lacks a device plane is refused."""
    import jax

    from benchmark import xplane

    def load(path):
        trace = real_load(path)
        if trace.devices:
            return trace
        ops = []
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:CPU"):
                continue
            for line in plane.lines:
                for e in line.events:
                    stats = dict(e.stats)
                    if e.duration_ns > 0 and "hlo_op" in stats:
                        ops.append(xplane.Event(
                            f"{stats.get('hlo_module')}/{e.name}",
                            e.start_ns, e.start_ns + e.duration_ns))
        if not ops:
            return trace
        mods = {}
        for e in ops:
            mods.setdefault(e.name.split("/")[0], []).append(e)
        modules = [xplane.Event(m, s, t) for m, evs in mods.items()
                   for s, t in xplane.union(evs)]
        was_op = {(e.start, e.end) for e in ops}
        host = [e for e in trace.host if (e.start, e.end) not in was_op]
        return xplane.Trace([xplane.DeviceLine("/host:CPU as a device",
                                               ops, modules)], host)
    return load
