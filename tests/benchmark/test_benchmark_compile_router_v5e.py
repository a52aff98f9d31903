"""Rehearsal compile of the router pool's step program (`router-100k`) for a
described v5e 2x2 with no chip attached, as
tests/benchmark/test_benchmark_compile_fanin_v5e.py does for the fan-in (the
same fixtures, copied: that file is the accepted benchmark's).

Nothing runs, so this says nothing about results or times: it catches what
the TPU's compiler refuses in the route stage ahead of the merge delivery,
and it reads off the optimized program what the cell's metrics rest on: the
rank's dots keep the scope `akka.route.rank` (a `reduce-window` would carry
none). Tier-1 compiles at 4,096 producers; the cell's own size is marked
slow (two minutes here)."""

import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

SIZES = [pytest.param(1 << 12, 200, id="4k"),
         pytest.param(1 << 20, 100_000, id="1m", marks=pytest.mark.slow)]


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


def scoped(text: str, opcode: str):
    """The `op_name` of every instruction of that opcode ('' where none)."""
    for line in text.splitlines():
        if re.search(rf" {re.escape(opcode)}\(", line):
            name = re.search(r'op_name="([^"]*)"', line)
            yield name.group(1) if name else ""


@pytest.mark.parametrize("n_producers,n_routees", SIZES)
def test_router_step_program_compiles_for_v5e(topo, as_tpu, n_producers,
                                              n_routees):
    """`router-100k`: BatchedSystem.run's program as the configuration
    builds it, the route stage ahead of dynamic delivery with `need_max`."""
    from akka_tpu.models.baseline_benches import build_router_pool
    from akka_tpu.ops import segment as sg
    from benchmark.harness import BENCH, load_json

    args = load_json(BENCH, "configs", "router-pool-100k.json")[
        "builder_args"]
    system = build_router_pool(**dict(args, n_producers=n_producers,
                                      n_routees=n_routees))
    assert system.need_max and system.topology is None and system.routers
    assert sg.choose_reduce_kernel(system.inbox_dst.shape[0],
                                   system.capacity, 4, "tpu") == "merge"
    one = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        system._carry())
    compiled = system._run_jit.lower(*shapes, 16,
                                     system._topo_arrays).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    text = compiled.as_text()
    assert text.count(" sort(") == 1  # the merge delivery, one sort
    assert "akka.route.readdress" in text and "akka.deliver.max" in text
    assert "scatter(" not in text and "gather(" not in text
    # the rank is dots, and they kept their path; the delivery's prefix sums
    # are reduce-windows, and the compiler gave them none
    dots = list(scoped(text, "convolution")) + list(scoped(text, "dot"))
    assert dots and all("/akka.route/akka.route.rank/" in p for p in dots)
    windows = list(scoped(text, "reduce-window"))
    assert windows and not any("akka.route" in p for p in windows)
