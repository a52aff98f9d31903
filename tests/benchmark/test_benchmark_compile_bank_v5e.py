"""Rehearsal compile of the bank's step program (`bank-ordered-1m`) for a
described v5e 2x2 with no chip attached, as
tests/benchmark/test_benchmark_compile_router_v5e.py does for the router
pool (the same fixtures, copied: that file is the accepted benchmark's).

Nothing runs, so this says nothing about results or times: it catches what
the TPU's compiler refuses in slots delivery, the fold's scan and the spill,
and it reads off the optimized program what the cell's metrics rest on: the
slots kernel's blocks and the account's branch keep their scopes. Tier-1
compiles at 4,096 tellers; the cell's own size is marked slow."""

import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

SIZES = [pytest.param(1 << 12, 1 << 9, 256, id="4k"),
         pytest.param(1 << 20, 1 << 17, 1 << 14, id="1m",
                      marks=pytest.mark.slow)]
BLOCKS = ("akka.deliver.sort", "akka.deliver.rank", "akka.deliver.place",
          "akka.deliver.spill", "akka.deliver.reduce",
          "akka.behavior.account", "akka.behavior.teller", "akka.emit.spill")


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


def compile_for(topo, system, steps=16):
    one = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        system._carry())
    return system._run_jit.lower(*shapes, steps,
                                 system._topo_arrays).compile()


@pytest.mark.parametrize("n_tellers,n_accounts,spill", SIZES)
def test_bank_step_program_compiles_for_v5e(topo, as_tpu, n_tellers,
                                            n_accounts, spill):
    """`bank-ordered-1m`: BatchedSystem.run's program as the configuration
    builds it, `delivery_backend` left to `auto`, which on a TPU is the
    wide-sort slots kernel."""
    from akka_tpu.models.baseline_benches import build_bank
    from akka_tpu.ops import segment as sg
    from benchmark.harness import BENCH, load_json

    conf = load_json(BENCH, "configs", "bank-accounts-128k.json")
    args = dict(conf["builder_args"], n_tellers=n_tellers,
                n_accounts=n_accounts, spill_capacity=spill)
    assert "delivery_backend" not in args
    system = build_bank(**args)
    assert system.mailbox_slots == 16 and system.spill_cap == spill
    assert system.inbox_dst.shape[0] == spill + n_accounts + n_tellers + 8
    assert sg._backend_impl(None, "tpu") == "wide"
    compiled = compile_for(topo, system)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    text = compiled.as_text()
    for block in BLOCKS:
        assert f"/{block}/" in text or f"/{block}\"" in text, block
    # one wide sort carries every column; the compiler sorts the indices of
    # some of the seven scatters again, and those sorts keep the block's name
    sorts = list(scoped(text, "sort"))
    assert sum("/akka.deliver.sort/" in p for p in sorts) == 1
    assert all("/akka.deliver.sort/" in p or "/akka.deliver.place/" in p
               or "/akka.deliver.spill/" in p for p in sorts), sorts
    assert len(list(scoped(text, "scatter"))) == 7
    # the fold is a loop of 16 inside the behavior's layer
    assert re.search(r'op_name="[^"]*akka\.behavior\.account[^"]*while',
                     text)
