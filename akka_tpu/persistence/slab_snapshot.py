"""Checkpoint/resume of the TPU-batched runtime: snapshot the SoA slabs.

SURVEY.md §2.10 item 8 / §5 checkpoint-resume: "snapshot = dump of SoA state
tensors (orbax), journal = append-only host log of message batches; replay =
re-running jitted steps". This module is that snapshot half for
akka_tpu.batched.BatchedSystem: every device-resident slab (per-column actor
state, behavior ids, alive mask, inbox tensors, step counter, supervision
counters, attention word) is serialized as one pytree.

Schema v3 (docs/CHECKPOINT_RECOVERY.md has the full layout): v1 carried only
the seven core slabs and silently dropped the supervision aggregates added
since — a restore of a v1 snapshot into a supervised system would resume
with whatever stale counters the target happened to hold. v2 adds
`mail_dropped`, `sup_counts`, `attention` and the sharded `dropped` block
plus an explicit `schema_version` field. v3 adds the telemetry plane:
the `metrics` histogram slab and the `inbox_enq` enqueue-step column
(docs/OBSERVABILITY.md) — both are derived telemetry whose shapes depend
on whether metrics are compiled in, so on shape mismatch they zero-fill
instead of failing the restore (like `attention`). The loader still
accepts v1/v2 snapshots and ZERO-FILLS (with `reserved_fill`) every live
slab the snapshot does not carry, so the restored state is a pure function
of the snapshot file, never of the pre-restore target.

Uses orbax-checkpoint when importable (async-friendly, TPU-native sharding
aware) and falls back to a .npz file — the pytree layout is identical, so
the two formats are feature-equivalent for single-host slabs. The .npz
fallback writes tmp + fsync + os.replace, so a crash mid-save leaves the
previous snapshot intact instead of a torn file.

Journal-side replay integration: JournalPlugin stores inbox batches via
`record_step_batch`, and `replay_steps` re-applies them to a restored system
— the reference's event replay (persistence/Eventsourced.scala recovery)
with "event" = one step's message batch. The write-ahead tell journal
(persistence/tell_journal.py) is the crash-recovery counterpart: staged
batches are logged BEFORE enqueue and replayed past the snapshot's step.
"""

from __future__ import annotations

import os
import shutil
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

SCHEMA_VERSION = 3

# v1 slabs: core actor/inbox tensors (pre-supervision snapshots carry only
# these).
_SLAB_KEYS_V1 = ("behavior_id", "alive", "step_count", "inbox_dst",
                 "inbox_type", "inbox_payload", "inbox_valid")
# v2 additions: supervision aggregates + the attention word. `dropped`
# exists only on ShardedBatchedSystem; getattr-None skips it elsewhere.
_SLAB_KEYS_V2 = ("mail_dropped", "sup_counts", "attention", "dropped")
# v3 additions: the telemetry plane — the device metric slab and the
# per-row enqueue-step column feeding the sojourn histogram. Shapes vary
# with metrics_on / shard count, so mismatches zero-fill (see below).
# `spill_stats` (BatchedSystem's spilled / spill_high_water counters) joined
# them without a new version: a snapshot that lacks it restores zeros.
# `mesh_stats` is the mesh's: the same two a shard, and its exchange's
# high water (ShardedBatchedSystem; None, and skipped, where it keeps none).
_SLAB_KEYS_V3 = ("metrics", "inbox_enq", "spill_stats", "mesh_stats")
_SLAB_KEYS = _SLAB_KEYS_V1 + _SLAB_KEYS_V2 + _SLAB_KEYS_V3

# Derived telemetry, not source state: a layout change across runtimes
# zero-fills instead of raising, and the next step/drain repopulates it.
_ZERO_FILL_ON_MISMATCH = ("attention", "metrics", "inbox_enq")


def _reserved_fill(col: str) -> int:
    from ..batched.supervision import reserved_fill
    return reserved_fill(col)


def slab_pytree(system) -> Dict[str, Any]:
    """Extract the full device state of a BatchedSystem (or
    ShardedBatchedSystem) as a pytree of HOST copies. Copies are mandatory:
    the step functions donate their input buffers, so a snapshot of live
    device arrays would be deleted by the very next `run()`. Callers must
    quiesce first (`block_until_ready()`); the system-level `checkpoint()`
    entry points do."""
    tree: Dict[str, Any] = {
        "schema_version": np.int64(SCHEMA_VERSION),
        "state": {k: np.asarray(jax.device_get(v))
                  for k, v in system.state.items()}}
    for k in _SLAB_KEYS:
        v = getattr(system, k, None)
        # zero-size slabs (inbox_enq with metrics compiled out) are
        # omitted: tensorstore refuses empty params, and the restore path
        # zero-fills absent v3 keys anyway
        if v is not None and getattr(v, "size", 1) != 0:
            tree[k] = np.asarray(jax.device_get(v))
    return tree


def _put_like(system, arr, current) -> Any:
    """Re-place a restored array with the sharding its predecessor had
    (a sharded system's slabs must go back onto the mesh, not onto the
    default device). Sharding metadata survives donation, so `current`
    may be a deleted array and still answer .sharding."""
    a = jnp.asarray(arr)
    try:
        sharding = current.sharding
    except Exception:  # noqa: BLE001 — plain single-device system
        return a
    return jax.device_put(a, sharding)


def restore_slab_pytree(system, tree: Dict[str, Any]) -> None:
    """Load a pytree produced by slab_pytree back into `system` (shapes must
    match: same capacity/out_degree/payload schema).

    Version handling: snapshots without `schema_version` are v1. Any live
    state column or v2 slab the snapshot lacks is reset to its
    `reserved_fill` value — a v1 snapshot restored into a supervised system
    yields zeroed retry counters / re-armed backoff deadlines, not the
    target's stale pre-restore values. Snapshot columns the target does not
    declare are skipped (a behavior-schema change is the caller's problem,
    not a KeyError)."""
    version = int(np.asarray(tree.get("schema_version", 1)))
    if version > SCHEMA_VERSION:
        raise ValueError(
            f"snapshot schema v{version} is newer than this runtime's "
            f"v{SCHEMA_VERSION}; upgrade the runtime to restore it")
    for col, arr in tree["state"].items():
        cur = system.state.get(col)
        if cur is None:
            continue  # column no longer in the target's schema
        if tuple(cur.shape) != tuple(arr.shape):
            raise ValueError(
                f"slab shape mismatch for state[{col!r}]: "
                f"{tuple(arr.shape)} vs {tuple(cur.shape)}")
        system.state[col] = _put_like(system, arr, cur)
    for col, cur in list(system.state.items()):
        if col not in tree["state"]:
            # v1 upgrade path: supervision columns absent from the
            # snapshot reset to their re-arm fill, for determinism
            fill = jnp.full(cur.shape, _reserved_fill(col), cur.dtype)
            system.state[col] = _put_like(system, fill, cur)
    for k in _SLAB_KEYS:
        cur = getattr(system, k, None)
        if cur is None:
            continue  # slab the target does not have (e.g. `dropped`)
        if k in tree:
            arr = tree[k]
            if hasattr(cur, "shape") and tuple(cur.shape) != tuple(
                    np.asarray(arr).shape):
                if k in _ZERO_FILL_ON_MISMATCH:
                    # derived telemetry, not source state: a layout change
                    # (the 4-word pre-progress-lane attention format,
                    # per-shard rows from another mesh, or a metrics-on/off
                    # flip) zero-fills and the first restored step repacks
                    setattr(system, k, _put_like(
                        system, jnp.zeros(cur.shape, cur.dtype), cur))
                    continue
                raise ValueError(
                    f"slab shape mismatch for {k}: "
                    f"{np.asarray(arr).shape} vs {tuple(cur.shape)}")
            setattr(system, k, _put_like(system, arr, cur))
        elif k in _SLAB_KEYS_V2 or k in _SLAB_KEYS_V3:
            # older snapshot: the aggregate never existed — zero it
            fill = jnp.zeros(cur.shape, cur.dtype)
            setattr(system, k, _put_like(system, fill, cur))


def _try_orbax():
    try:
        import orbax.checkpoint as ocp
        return ocp
    except Exception:  # noqa: BLE001 — orbax optional at runtime
        return None


def save_slabs(system, directory: str, step: Optional[int] = None) -> str:
    """Snapshot `system` under `directory`; returns the checkpoint path."""
    return save_slab_tree(slab_pytree(system), directory, step)


def save_slab_tree(tree: Dict[str, Any], directory: str,
                   step: Optional[int] = None) -> str:
    """Serialize an already host-gathered slab pytree (`slab_pytree`
    output) under `directory`. Split from save_slabs so the hot re-shard
    path (sentinel.scale_to) can take the host copies at the drain barrier
    and overlap THIS — the fsync'd disk write — with the rebuild on the
    new mesh, restoring directly from the in-memory tree."""
    tree = jax.tree_util.tree_map(np.asarray, tree)
    ocp = _try_orbax()
    name = f"slab-{step if step is not None else int(tree['step_count'])}"
    path = os.path.join(os.path.abspath(directory), name)
    if ocp is not None:
        ckpt = ocp.PyTreeCheckpointer()
        ckpt.save(path, tree, force=True)
        return path
    os.makedirs(directory, exist_ok=True)
    flat = {"schema_version": tree["schema_version"]}
    for col, arr in tree["state"].items():
        flat[f"state.{col}"] = arr
    for k in _SLAB_KEYS:
        if k in tree:
            flat[k] = tree[k]
    # tmp + fsync + rename: a crash mid-save must not tear the snapshot a
    # recovery is about to depend on
    final = path + ".npz"
    tmp = final + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **flat)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, final)
    return final


def load_slab_tree(path: str) -> Dict[str, Any]:
    """Read a snapshot back as the host-side pytree (no system needed) —
    the re-sharding restore path inspects shapes before placement."""
    if path.endswith(".npz"):
        with np.load(path) as data:
            tree: Dict[str, Any] = {"state": {}}
            for k in data.files:
                if k.startswith("state."):
                    tree["state"][k[len("state."):]] = data[k]
                else:
                    tree[k] = data[k]
        return tree
    ocp = _try_orbax()
    if ocp is None:
        raise RuntimeError("orbax not available and path is not .npz")
    return ocp.PyTreeCheckpointer().restore(path)


def restore_slabs(system, path: str) -> None:
    """Restore a snapshot written by save_slabs into `system`."""
    restore_slab_pytree(system, load_slab_tree(path))


def _slab_step(name: str) -> Optional[int]:
    if not name.startswith("slab-"):
        return None
    stem = name[len("slab-"):]
    stem = stem[:-4] if stem.endswith(".npz") else stem
    try:
        return int(stem)
    except ValueError:
        return None


def latest_slab_path(directory: str) -> Optional[str]:
    if not os.path.isdir(directory):
        return None
    best, best_step = None, -1
    for name in os.listdir(directory):
        step = _slab_step(name)
        if step is not None and step > best_step:
            best, best_step = os.path.join(directory, name), step
    return best


def gc_slabs(directory: str, keep: int) -> int:
    """Retained-snapshot GC: delete all but the `keep` newest snapshots in
    `directory`. Returns how many were removed. Both the .npz fallback
    (files) and orbax (directories) layouts are handled."""
    if keep <= 0 or not os.path.isdir(directory):
        return 0
    entries = []
    for name in os.listdir(directory):
        step = _slab_step(name)
        if step is not None:
            entries.append((step, name))
    entries.sort(reverse=True)
    removed = 0
    for _step, name in entries[keep:]:
        full = os.path.join(directory, name)
        try:
            if os.path.isdir(full):
                shutil.rmtree(full)
            else:
                os.remove(full)
            removed += 1
        except OSError:
            pass  # concurrent GC / permissions: stale snapshot stays
    return removed
