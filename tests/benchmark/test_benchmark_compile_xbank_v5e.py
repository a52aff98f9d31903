"""Rehearsal compile of the sharded bank's step program (`xshard-bank-4chip`)
for a described v5e 2x2 with no chip attached, as
tests/benchmark/test_benchmark_compile_v5e.py does for the ring over the
mesh (the same fixtures, copied: that file is the accepted benchmark's).

Nothing runs, so this says nothing about results or times: it catches what
the TPU's compiler refuses in slots delivery inside `shard_map`, and it holds
the optimized program to what the CELL promises and no more: it fits a
chip's 16 GB, every block the cell's metrics and scope table name is there
under its name, and the type column rides the exchange (four collectives a
step). How many sorts, fusions or scatters the compiler makes of it is no
promise of the cell's, and is not asserted. Tier-1 compiles at 4,096
tellers; the cell's own size is marked slow."""

import os
import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

# tellers, accounts, logical shards, spill rows and pair rows a chip
SIZES = [pytest.param(1 << 12, 1 << 9, 8, 64, 1152, id="4k"),
         pytest.param(1 << 20, 1 << 17, 256, 4096, 73728, id="1m",
                      marks=pytest.mark.slow)]
BLOCKS = ("akka.exchange.bucket", "akka.exchange.all_to_all",
          "akka.exchange.unpack", "akka.deliver.sort", "akka.deliver.kind",
          "akka.deliver.rank", "akka.deliver.place", "akka.deliver.spill",
          "akka.deliver.reduce", "akka.behavior.account",
          "akka.behavior.teller", "akka.emit.spill")


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


def compile_on(topo, system, steps):
    """The system's own step builder over a mesh of the described chips,
    lowered from the shapes of what `run` hands it."""
    real = system.mesh
    system.mesh = Mesh(np.asarray(topo.devices[:4]).reshape(
        real.devices.shape), real.axis_names)
    try:
        step = system._build_step(system.stray_mode)

        def shape(a):
            spec = a.sharding.spec if isinstance(a.sharding, NamedSharding) \
                else P()
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(system.mesh, spec))
        carry, tables, stats = jax.tree_util.tree_map(
            shape, (system._carry(), system.tables, system._kept_stats()))
        return step.lower(*carry, tables, steps, *stats).compile()
    finally:
        system.mesh = real


@pytest.mark.parametrize("n_tellers,n_accounts,shards,spill,pair", SIZES)
def test_sharded_bank_step_program_compiles_for_four_v5e(
        topo, as_tpu, n_tellers, n_accounts, shards, spill, pair):
    """`xshard-bank-4chip`: ShardedBatchedSystem.run's program as the
    configuration builds it over 4 chips, `delivery_backend` left to `auto`,
    which on a TPU is the wide slots family and the sorted exchange."""
    from akka_tpu.models.baseline_benches import build_bank_sharded
    from akka_tpu.ops import segment as sg
    from benchmark.harness import BENCH, load_json

    conf = load_json(BENCH, "configs", "bank-sharded-128k.json")
    traffic = load_json(BENCH, "traffic", "bank-commands-mesh.json")
    args = dict(conf["builder_args"], n_tellers=n_tellers,
                n_accounts=n_accounts, n_shards=shards, spill_capacity=spill,
                remote_capacity_per_pair=pair, n_devices=4)
    assert "delivery_backend" not in args
    system = build_bank_sharded(**args)
    assert system.mailbox_slots == 16 and system.mesh_stats is not None
    assert system.inbox_dst.shape[0] == 4 * (spill + 4 * pair + 8)
    if n_tellers == conf["tellers"]:
        assert system.inbox_dst.shape[0] == 4 * conf["inbox_rows_per_chip"]
    assert sg._backend_impl(None, "tpu") == "wide"
    assert not sg.exchange_uses_ranked("tpu", None)
    compiled = compile_on(topo, system, int(traffic["chunk_steps"]))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    text = compiled.as_text()
    for block in BLOCKS:
        assert f"/{block}/" in text or f"/{block}\"" in text, block
    # dst, type, payload and the valid flags each cross the chips
    exchanged = re.findall(r" all-to-all(?:-start)?\(", text)
    assert len(exchanged) == 4, len(exchanged)
    assert all("akka.exchange.all_to_all" in line
               for line in text.splitlines()
               if re.search(r" all-to-all(-start)?\(", line))
