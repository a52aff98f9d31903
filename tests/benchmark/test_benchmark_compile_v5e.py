"""Rehearsal compiles: each cell's step program, compiled for a described
v5e 2x2 with no chip attached (on-chip-measurement guide, section 2.3).

Nothing runs, so this says nothing about results or times: it catches what
the TPU's compiler refuses. The program picks its delivery kernels from
`jax.default_backend()` while tracing, which here says "cpu"; the test steers
that to "tpu" itself, so the kernels compiled are the ones the chip runs.
Tier-1 compiles at 4,096 rows (the full programs take minutes to compile);
the cells' own sizes are marked slow. One file, topology inside a fixture."""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
from jax.sharding import (NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

SIZES = [pytest.param(1 << 12, id="4k"),
         pytest.param(1 << 20, id="1m", marks=pytest.mark.slow)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def as_tpu(monkeypatch):
    """Tracing code that asks for the platform hears 'tpu'; the persistent
    cache is off, since an entry compiled here cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _shapes(tree, sharding_of):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=sharding_of(a)), tree)



@pytest.mark.parametrize("n", SIZES)
def test_ring_step_program_compiles_for_v5e(topo, as_tpu, n):
    """`ring-dynamic-1m`: BatchedSystem.run's program, dynamic delivery."""
    from akka_tpu.models.baseline_benches import build_ring
    from akka_tpu.ops import segment as sg

    system = build_ring(n, static=False)
    assert sg.choose_reduce_kernel(system.inbox_dst.shape[0], n, 4,
                                   "tpu") != "scatter"
    one = SingleDeviceSharding(topo.devices[0])
    args = _shapes(system._carry(), lambda a: one)
    compiled = system._run_jit.lower(*args, 16, system._topo_arrays).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    assert "sort" in compiled.as_text()  # the merge delivery, not scatter


def _mesh_args(system, topo_mesh):
    def sharding_of(a):
        spec = a.sharding.spec if isinstance(a.sharding, NamedSharding) \
            else P()
        return NamedSharding(topo_mesh, spec)

    carry = (system.state, system.behavior_id, system.alive,
             system.inbox_dst, system.inbox_type, system.inbox_payload,
             system.inbox_valid, system.inbox_enq, system.dropped,
             system.mail_dropped, system.sup_counts, system.metrics,
             system.step_count, system.tables)
    return _shapes(carry, sharding_of)


def _with_mesh(system, devices):
    """The system's own step builder, over a mesh of described devices."""
    import numpy as np
    from jax.sharding import Mesh

    real = system.mesh
    system.mesh = Mesh(np.asarray(devices).reshape(real.devices.shape),
                       real.axis_names)
    try:
        return system._build_step(system.stray_mode), system.mesh
    finally:
        system.mesh = real


@pytest.mark.parametrize("n", SIZES)
def test_cross_shard_step_program_compiles_for_four_v5e(topo, as_tpu, n):
    """`xshard-ring-4chip`: ShardedBatchedSystem's step over 4 chips, with
    its all_to_all."""
    from akka_tpu.models.baseline_benches import build_cross_shard

    system = build_cross_shard(n // 4096, 4096, n_devices=4)
    step, mesh = _with_mesh(system, topo.devices[:4])
    compiled = step.lower(*_mesh_args(system, mesh), 16).compile()
    assert "all-to-all" in compiled.as_text()
