"""The ordered-mailbox enqueue as the TPU's compiler leaves it: the bank's
step program (`bank-ordered-1m`, slots delivery, the wide family) compiled
for a described v5e 2x2 with no chip attached. The fixtures are those of
tests/benchmark/test_benchmark_compile_bank_v5e.py, copied as that file
copied the router's: that file is the accepted benchmark's.

Nothing runs, so this says nothing about results or times. It holds what the
enqueue of `_deliver_slots_wide` is built on: after the one wide sort the
slots and the spill region are filled by shift-and-select routings of the
sorted rows, so the program holds NO scatter (a TPU takes a scatter's update
rows one by one) and no second sort (the compiler sorts a scatter's indices),
and every operation of the enqueue keeps `akka.deliver.place` or
`akka.deliver.spill` as its deepest scope, which the cell's
`bank_place_share`, `bank_spill_share` and `bank_place_roofline` read. Since
PR 37 the recipients' `slots_kind` flags reach the sorted rows by such
routings too (`akka.deliver.kind`), so the program holds NO gather either (a
TPU takes a gather's rows one by one as well) and the sort seven operands.
Tier-1 compiles at 4,096 tellers; the cell's own size is marked slow."""

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
          "akka.deliver.kind", "akka.deliver.spill", "akka.deliver.reduce",
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
    cache is off, since an entry compiled here cannot be read back. JAX's
    own caches are emptied before and after, as tests/conftest.py does for
    tests/benchmark: this test came out red once on a worker of the whole
    suite and green alone (PR 36), so what its assertions read of the
    `op_name`s depended on what the worker had traced before; and what is
    traced here under the 'tpu' answer must not reach a later test."""
    import gc

    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    gc.collect()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()
    jax.clear_caches()
    gc.collect()


def op_names(text: str, opcode: str):
    """The `op_name` of every instruction of that opcode ('' where none)."""
    for line in text.splitlines():
        if re.search(rf" {re.escape(opcode)}\(", line):
            name = re.search(r'op_name="([^"]*)"', line)
            yield name.group(1) if name else ""


def sort_operands(text: str):
    """The number of operands of every `sort(` instruction."""
    for line in text.splitlines():
        found = re.search(r" sort\(([^)]*)\)", line)
        if found:
            yield len(re.findall(r"%[\w.\-]+", found.group(1)))


def deepest_scope(path: str) -> str:
    """The last `akka.` component of an operation's path: the block that
    benchmark/xscope.py::layer_of files its time under."""
    parts = [c for c in path.split("/") if c.startswith("akka.")]
    return parts[-1] if parts else ""


def compile_for(topo, system, steps=16):
    one = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        system._carry())
    return system._run_jit.lower(*shapes, steps,
                                 system._topo_arrays).compile()


@pytest.mark.parametrize("n_tellers,n_accounts,spill", SIZES)
def test_bank_enqueue_compiles_without_scatter_for_v5e(topo, as_tpu,
                                                       n_tellers, n_accounts,
                                                       spill):
    """`BatchedSystem.run`'s program as `build_bank` makes it,
    `delivery_backend` left to `auto`, which on a TPU is the wide family."""
    from akka_tpu.models.baseline_benches import build_bank
    from akka_tpu.ops import segment as sg

    system = build_bank(n_tellers=n_tellers, n_accounts=n_accounts,
                        spill_capacity=spill)
    assert system.mailbox_slots == 16 and system.spill_cap == spill
    assert system.delivery_backend in (None, "auto")
    assert sg._backend_impl(system.delivery_backend, "tpu") == "wide"
    compiled = compile_for(topo, system)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    text = compiled.as_text()
    for block in BLOCKS:
        assert f"/{block}/" in text or f"/{block}\"" in text, block
    # no scatter anywhere in the step, and so no sort of scatter indices:
    # the one sort is the wide sort that carries every column; and no gather
    assert list(op_names(text, "scatter")) == []
    assert list(op_names(text, "gather")) == []
    sorts = list(op_names(text, "sort"))
    assert len(sorts) == 1 and deepest_scope(sorts[0]) == "akka.deliver.sort"
    # key, arrival index, type and four payload columns: the flags no
    # longer ride
    assert list(sort_operands(text)) == [7]
    # the enqueue's operations are filed under the two blocks the cell's
    # metrics read: no routing helper opens a scope of its own beneath them
    deepest = {deepest_scope(name)
               for op in ("fusion", "select", "concatenate", "copy")
               for name in op_names(text, op)}
    assert {"akka.deliver.place", "akka.deliver.spill"} <= deepest, deepest
    # the fold is a loop of 16 inside the behavior's layer
    assert re.search(r'op_name="[^"]*akka\.behavior\.account[^"]*while',
                     text)


def shaped(text: str, opcode: str):
    """(result shape, deepest `akka.` scope) of every instruction of that
    opcode, the shape as written (`s32[16,1024]`), layout left off."""
    for line in text.splitlines():
        found = re.search(rf"= (\w+\[[\d,]*\])\S* {re.escape(opcode)}\(",
                          line)
        if found:
            name = re.search(r'op_name="([^"]*)"', line)
            yield found.group(1), deepest_scope(name.group(1) if name
                                                else "")


@pytest.mark.parametrize("n_tellers,n_accounts,spill", SIZES)
def test_bank_enqueue_routes_into_the_slots_extent_block_for_v5e(
        topo, as_tpu, n_tellers, n_accounts, spill):
    """The slots extent: `build_bank` puts its accounts, the one slots
    behavior, in rows [0, A), so the enqueue fills the slots of R = A rows
    (rounded up to 1,024) and not of N = A + T. It does so in one
    routing, with no loop: a compress of the taken rows over the M sorted
    rows, then an expand into the row-major [R*S] slot block. The N-wide
    block stays in the program as the fallback a `lax.cond` takes where a
    live row past R takes slots, under `akka.deliver.place_all`."""
    from akka_tpu.models.baseline_benches import build_bank

    system = build_bank(n_tellers=n_tellers, n_accounts=n_accounts,
                        spill_capacity=spill)
    n, r = system.capacity, max(n_accounts, 1024)
    assert system._core.slots_extent == r < n
    text = compile_for(topo, system).as_text()
    assert system.host_stats()["slots_extent"] == r
    assert "/akka.deliver.place_all/" in text
    rows = {(shape, scope) for op in ("select", "fusion")
            for shape, scope in shaped(text, op)}
    # the expand's passes select over the R*S slot rows, the fallback's
    # over N*S, and nothing of the fast path is N*S rows long
    assert (f"s32[{16 * r}]", "akka.deliver.place") in rows
    assert (f"s32[{16 * n}]", "akka.deliver.place_all") in rows
    assert (f"s32[{16 * n}]", "akka.deliver.place") not in rows
    # the S turns are gone: no loop is filed under the enqueue
    loops = {deepest_scope(name) for name in op_names(text, "while")}
    assert "akka.deliver.place" not in loops
    assert "akka.deliver.place_all" not in loops


def leading(text: str):
    """(leading dimension, deepest `akka.` scope) of every instruction
    whose result is an array with an `op_name`."""
    for line in text.splitlines():
        found = re.search(r"= \w+\[(\d+)[,\]]", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if found and name:
            yield int(found.group(1)), deepest_scope(name.group(1))


@pytest.mark.parametrize("n_tellers,n_accounts,spill", SIZES)
def test_bank_fold_runs_the_slots_extent_wide_for_v5e(topo, as_tpu,
                                                      n_tellers, n_accounts,
                                                      spill):
    """The behavior switch over the slots extent: rows [0, R) run every
    branch, the fold among them, and the rows past R a switch whose slots
    branch is left out, so no operation of the account's fold is N wide;
    the N-wide switch stays in the program as the fallback of a step in
    which a live row past R takes slots, its blocks `<name>.all`."""
    from akka_tpu.models.baseline_benches import build_bank

    system = build_bank(n_tellers=n_tellers, n_accounts=n_accounts,
                        spill_capacity=spill)
    n, r = system.capacity, max(n_accounts, 1024)
    text = compile_for(topo, system).as_text()
    rows = set(leading(text))
    account = {dim for dim, scope in rows if scope == "akka.behavior.account"}
    assert r in account and n not in account and n - r not in account
    assert (n, "akka.behavior.account.all") in rows
    assert (n - r, "akka.behavior.teller") in rows
    assert re.search(r'op_name="[^"]*akka\.behavior\.account\.all[^"]*'
                     r'while', text)
