"""A layer's share of the device's busy time: self time of the step
program's operations traced under one `jax.named_scope` layer (`scope`:
`akka.deliver`, `akka.behavior`, `akka.exchange`; `null` for those under
none), over the busy time of the traced stretch, fullest device, in percent.

The first metric of a run builds the whole table and prints it to stderr:
every layer and block with its seconds, its share and its three largest
operations, so that `fusion.36` reads as a line under `akka.exchange.bucket`.

A program that names no layers (one from before the scopes) reads 0 under
every layer and all of its time under none. A program that does name them
and whose operations carry none ran an executable compiled before the
scopes were there, out of the compile cache: that is an error."""

from __future__ import annotations

import re
import sys

from benchmark import harness, xscope


def _program_names_layers() -> bool:
    try:
        from akka_tpu.batched.step import SCOPE_LAYERS
    except ImportError:
        return False
    return bool(SCOPE_LAYERS)


def _table(obs: dict, modules) -> dict:
    """{"busy": s, "step": s, "layers": {layer: s}, "rows": [...]} of the
    step programs named by `modules`; built once a run and kept in `obs`."""
    key = ("scope_table",) + tuple(modules)
    if key in obs:
        return obs[key]
    # the harness hands readers no path yet: until it does, search for it
    path = obs.get("trace_path") or xscope.find_trace_file(obs["trace_a"])
    scopes = xscope.read_scopes(path)
    sums = xscope.scope_sums(obs["trace"], scopes, obs["trace_a"],
                             obs["trace_b"], obs["trace_fullest"])
    busy = sum(v for ops in sums.values() for v in ops.values())
    layers, rows = {}, []
    for (layer, block), ops in sums.items():
        mine = {n: v for n, v in ops.items()
                if any(re.search(p, n.split("/", 1)[0]) for p in modules)}
        if not mine:
            continue
        seconds = sum(mine.values())
        layers[layer] = layers.get(layer, 0.0) + seconds
        rows.append((layer, block, seconds,
                     sorted(mine.items(), key=lambda kv: -kv[1])[:3]))
    table = {"busy": busy, "step": sum(layers.values()), "layers": layers,
             "rows": sorted(rows, key=lambda r: (-layers[r[0]], -r[2]))}
    named = set(layers) - {xscope.UNSCOPED}
    if table["step"] > 0 and not named and _program_names_layers():
        raise harness.BenchError(
            "the step program's operations carry no `akka.` scope: an "
            "executable older than the scopes was loaded from the compile "
            f"cache at {harness.compile_cache_dir()}")
    _print(table)
    obs[key] = table
    return table


def _print(table: dict) -> None:
    busy = table["busy"]
    lines = [f"step program by layer, self time on the fullest device "
             f"(busy {busy:.4f} s, step program {table['step']:.4f} s):"]
    seen = set()
    for layer, block, seconds, top in table["rows"]:
        if layer not in seen:
            seen.add(layer)
            total = table["layers"][layer]
            lines.append(f"  {layer:<28} {total:9.4f} s "
                         f"{100 * total / busy:6.2f}%")
        ops = ", ".join(f"{n} {v:.4f} s" for n, v in top)
        lines.append(f"    {block:<26} {seconds:9.4f} s "
                     f"{100 * seconds / busy:6.2f}%  {ops}")
    print("\n".join(lines), file=sys.stderr, flush=True)


def read(obs, modules, scope):
    table = _table(obs, modules)
    if table["busy"] <= 0 or table["step"] <= 0:
        return None
    seconds = table["layers"].get(scope or xscope.UNSCOPED, 0.0)
    return 100.0 * seconds / table["busy"]
