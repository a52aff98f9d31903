"""Tensor-native bulk CRDT kernels: the TPU-first data plane for ddata.

SURVEY.md §7 step 8: "G/PN counters and OR-sets have natural tensor
encodings (per-node counter rows; merge = elementwise max/sum — literally
psum-shaped)". The host Replicator (replicator.py) is the control plane for
arbitrary keys; when an application has MANY counters/flags/sets (e.g. one
per entity), it should hold them as a *bank*: a single device array with one
row per key and one column per cluster node. Merging two replicas of a bank
is then one fused elementwise op on the MXU-adjacent VPU, and converging all
replicas across a mesh axis is a single XLA collective (`lax.pmax` — the
max-reduction sibling of psum) instead of N² host gossip rounds.

Layouts (n_keys rows is the vmap/shard axis; n_nodes is small and fixed):
- GCounterBank:  uint32[n_keys, n_nodes]        merge = max, value = row sum
- PNCounterBank: uint32[n_keys, 2, n_nodes]     [:,0]=incs [:,1]=decs
- GSetBank:      bool[n_keys, n_elems]          merge = or, fixed universe
- FlagBank:      bool[n_keys]                   merge = or

No reference-file analogue exists for this module — it is the TPU-native
replacement for akka-distributed-data's per-object JVM merges
(ddata/GCounter.scala merge loop) at bank granularity.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# -- single-replica pairwise merges (jitted, fuse into one kernel) ----------

@jax.jit
def gcounter_merge(a: jax.Array, b: jax.Array) -> jax.Array:
    """Pairwise max over per-node rows (GCounter.scala merge semantics)."""
    return jnp.maximum(a, b)


@jax.jit
def gcounter_value(bank: jax.Array) -> jax.Array:
    """Per-key counter value: sum over the node axis."""
    return jnp.sum(bank, axis=-1)


@jax.jit
def pncounter_merge(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.maximum(a, b)


@jax.jit
def pncounter_value(bank: jax.Array) -> jax.Array:
    s = jnp.sum(bank, axis=-1)  # [n_keys, 2]
    return s[..., 0].astype(jnp.int64 if jax.config.jax_enable_x64
                            else jnp.int32) - s[..., 1]


@jax.jit
def gset_merge(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.logical_or(a, b)


flag_merge = gset_merge


def gcounter_increment(bank: jax.Array, node_slot: int,
                       key_ids: jax.Array, amounts: jax.Array) -> jax.Array:
    """Batched local increment: bump this node's column for each key in
    `key_ids` by `amounts`. Duplicate key_ids accumulate (scatter-add)."""
    return bank.at[key_ids, node_slot].add(amounts.astype(bank.dtype))


# -- mesh-wide convergence: one collective instead of gossip ----------------

def converge_over_mesh(bank: jax.Array, mesh: Mesh, axis: str = "replica",
                       op: str = "max") -> jax.Array:
    """All-replica merge of a replicated bank over a mesh axis.

    Each device along `axis` holds its own replica of the full bank (the
    ddata model: every node has a copy). One `lax.pmax` (or `pmax`-of-or for
    boolean banks) converges every replica to the join of all — the
    ICI-collective equivalent of WriteAll+ReadAll consistency.
    """
    reduce = {"max": jax.lax.pmax, "or": lambda x, ax: jax.lax.pmax(
        x.astype(jnp.uint8), ax).astype(jnp.bool_)}[op]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=P(axis),   # stacked replicas: leading axis = replica id
        out_specs=P(axis))
    def _converge(local):
        merged = reduce(local, axis)
        return merged

    return _converge(bank)


def replicate_bank(bank: jax.Array, mesh: Mesh, axis: str = "replica") -> jax.Array:
    """Stack one replica of `bank` per device along `axis` (test/bootstrap
    helper: real deployments start each node with its own local bank)."""
    n = mesh.shape[axis]
    stacked = jnp.broadcast_to(bank[None], (n,) + bank.shape)
    return jax.device_put(stacked, NamedSharding(mesh, P(axis)))
