"""The step program names its layers (`jax.named_scope`, batched/step.py
SCOPE_LAYERS) and both step drivers leave a host span with the steps they
launched: read here from compiled HLO text, lowered text and a CPU profiler
trace. What the names cost on the device is the benchmark's business."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_tpu.batched import BatchedSystem
from akka_tpu.batched.sharded import ShardedBatchedSystem
from akka_tpu.batched.step import SCOPE_LAYERS
from akka_tpu.models.baseline_benches import (PAYLOAD_W, build_cross_shard,
                                              make_crossshard_behavior,
                                              ring_behavior, seed_ring_full)
from akka_tpu.ops import segment as sg


def instructions(hlo_text: str):
    """(opcode, op_name) of every instruction of an HLO module's text that
    carries an `op_name`, fused computations' bodies included."""
    for line in hlo_text.splitlines():
        m = re.search(r" = .*?[\]})] ([a-z][a-z\-]*)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if m and name:
            yield m.group(1), name.group(1)


def layers_of(hlo_text: str) -> set:
    return {c for _, path in instructions(hlo_text) for c in path.split("/")
            if c.startswith("akka.")}


@pytest.fixture(scope="module")
def ring():
    """The 4,096-row dynamic ring on the kernels the chip runs (the wide
    merge; `auto` picks scatter on the CPU)."""
    s = BatchedSystem(capacity=4096, behaviors=[ring_behavior],
                      payload_width=PAYLOAD_W, host_inbox=8,
                      delivery="merge", delivery_backend="reference")
    s.spawn_block(ring_behavior, 4096)
    seed_ring_full(s)
    return s


@pytest.fixture(scope="module")
def mesh_ring():
    s = build_cross_shard(4, 1024, n_devices=4)
    s.run(1)  # builds the step
    s.block_until_ready()
    return s


@pytest.fixture(scope="module")
def ring_hlo(ring):
    return ring._run_jit.lower(*ring._carry(), 2,
                               ring._topo_arrays).compile().as_text()


def _mesh_step_hlo(s) -> str:
    return s._step_fn.lower(
        s.state, s.behavior_id, s.alive, s.inbox_dst, s.inbox_type,
        s.inbox_payload, s.inbox_valid, s.inbox_enq, s.dropped,
        s.mail_dropped, s.sup_counts, s.metrics, s.step_count, s.tables,
        2).compile().as_text()


@pytest.fixture(scope="module")
def mesh_hlo(mesh_ring):
    return _mesh_step_hlo(mesh_ring)


@pytest.fixture(scope="module")
def mesh_reference_hlo():
    """The same mesh ring on the kernels the chip runs: `mesh_ring` builds
    `auto`, which on the CPU is the ranked side of the exchange's seam."""
    b = make_crossshard_behavior(1024)
    s = ShardedBatchedSystem(capacity=4096, behaviors=[b], n_devices=4,
                             payload_width=PAYLOAD_W, host_inbox_per_shard=8,
                             delivery_backend="reference")
    s.spawn_block(b, 4096)
    s.run(1)  # builds the step
    s.block_until_ready()
    return _mesh_step_hlo(s)


def test_ring_run_program_has_its_one_sort_under_deliver(ring_hlo):
    sorts = [path for op, path in instructions(ring_hlo) if op == "sort"]
    assert len(sorts) == 1, sorts
    assert "/akka.deliver/akka.deliver.merge_sort/" in sorts[0]


@pytest.mark.parametrize("block", ["compact", "expand"])
def test_ring_run_program_routes_its_segment_ends_by_shifts(ring_hlo, block):
    # shift-and-select passes where a second sort, a gather or a scatter
    # would be: the segment ends compressed to the dense front, and the
    # dense rows expanded to their actors' rows, by selects and slices
    # (or the pads XLA makes of them), nothing data-addressed
    ops = {op for op, path in instructions(ring_hlo)
           if f"/akka.deliver/akka.deliver.{block}/" in path}
    assert "select" in ops and ops & {"slice", "pad", "concatenate"}, ops
    assert not ops & {"sort", "gather", "scatter", "dynamic-slice"}, ops


def test_ring_run_program_has_a_select_under_behavior(ring_hlo):
    assert any(op == "select" and "/akka.behavior/" in path
               for op, path in instructions(ring_hlo))


def test_ring_run_program_names_only_declared_layers(ring_hlo):
    named = {c for c in layers_of(ring_hlo) if c.count(".") == 1}
    assert {"akka.deliver", "akka.behavior", "akka.emit",
            "akka.attention"} <= named <= set(SCOPE_LAYERS)


def test_scopes_sit_inside_the_scan_body(ring_hlo):
    # one set of names serves every program built on StepCore: the path
    # runs jit(...)/while/body/.../akka.<layer>/...
    path = next(p for op, p in instructions(ring_hlo) if op == "sort")
    assert path.startswith("jit(_run_impl)/while/body/")


def test_mesh_step_has_its_all_to_all_under_exchange(mesh_hlo):
    a2a = [path for op, path in instructions(mesh_hlo)
           if op == "all-to-all"]
    assert a2a and all(
        "/akka.exchange/akka.exchange.all_to_all/" in p for p in a2a), a2a


def test_mesh_step_buckets_by_one_sort_and_contiguous_copies(
        mesh_reference_hlo):
    # the sorted side of the seam: each destination chip's rows are one run
    # of the keyed sort, so the send buffers are slices of it — nothing
    # data-addressed row by row
    ops = [op for op, path in instructions(mesh_reference_hlo)
           if "/akka.exchange/akka.exchange.bucket/" in path]
    assert ops.count("sort") == 1, ops
    assert "dynamic-slice" in ops and "select" in ops
    assert not {"scatter", "gather"} & set(ops), ops


def test_mesh_step_on_the_ranked_side_still_scatters(mesh_hlo):
    # the other side has no sorted rows to slice: it keeps its scatters
    ops = {op for op, path in instructions(mesh_hlo)
           if "/akka.exchange/akka.exchange.bucket/" in path}
    assert "scatter" in ops and "sort" not in ops, ops


def test_mesh_step_names_bucket_unpack_deliver_and_behavior(mesh_hlo):
    named = layers_of(mesh_hlo)
    assert {"akka.exchange.bucket", "akka.exchange.all_to_all",
            "akka.exchange.unpack", "akka.deliver",
            "akka.behavior"} <= named
    assert {c for c in named if c.count(".") == 1} <= set(SCOPE_LAYERS)


def test_fan_in_step_names_a_block_per_behavior_and_the_max():
    """Two behaviors under the vmapped switch: each branch's operations
    carry `akka.behavior.<name>` as a whole component of the path (the vmap
    wraps the `row` scope in its place), under the layer; the merge
    delivery's segmented max is `akka.deliver.max`."""
    from akka_tpu.models.baseline_benches import build_fan_in
    s = build_fan_in(4096, 16, static=False, delivery="merge",
                     delivery_backend="reference")
    hlo = s._run_jit.lower(*s._carry(), 2, s._topo_arrays).compile().as_text()
    paths = {path for _, path in instructions(hlo)}
    for block in ("akka.behavior.leaf", "akka.behavior.collector"):
        mine = [p for p in paths if f"/{block}/" in p]
        assert mine and all("/akka.behavior/vmap(row)/" in p for p in mine)
    named = layers_of(hlo)
    assert {"akka.behavior.leaf", "akka.behavior.collector",
            "akka.deliver.max", "akka.deliver.merge_sort",
            "akka.deliver.compact", "akka.deliver.expand"} <= named
    assert {c for c in named if c.count(".") == 1} <= set(SCOPE_LAYERS)
    sorts = [path for op, path in instructions(hlo) if op == "sort"]
    assert len(sorts) == 1 and "/akka.deliver.merge_sort/" in sorts[0]


def test_router_pool_step_names_the_route_layer_and_its_blocks():
    """The route stage ahead of delivery: `akka.route` with the blocks
    `rank` (the prefix count over the inbox, as dots: they keep the path
    where a cumsum's reduce-window loses it on the TPU) and `readdress`
    (the modulo and the router row's counters), before the one sort."""
    from akka_tpu.models.baseline_benches import build_router_pool
    s = build_router_pool(4096, 200, delivery="merge",
                          delivery_backend="reference")
    hlo = s._run_jit.lower(*s._carry(), 2, s._topo_arrays).compile().as_text()
    named = layers_of(hlo)
    assert {"akka.route", "akka.route.rank", "akka.route.readdress",
            "akka.behavior.routee", "akka.behavior.producer",
            "akka.deliver.merge_sort", "akka.deliver.max"} <= named
    assert {c for c in named if c.count(".") == 1} <= set(SCOPE_LAYERS)
    by_op = {}
    for op, path in instructions(hlo):
        by_op.setdefault(op, []).append(path)
    # every dot is a prefix sum and kept its path: the stage's rank and,
    # since PR 33, the merge delivery's running sums
    rank = [p for p in by_op["dot"] if "/akka.route/akka.route.rank/" in p]
    sums = [p for p in by_op["dot"]
            if "/akka.deliver/akka.deliver.prefix/" in p]
    assert rank and sums and len(rank) + len(sums) == len(by_op["dot"])
    assert " reduce-window(" not in hlo
    assert any("/akka.route/akka.route.readdress/" in p
               for p in by_op["remainder"])
    assert len(by_op["sort"]) == 1
    assert "/akka.deliver/akka.deliver.merge_sort/" in by_op["sort"][0]


def test_ring_run_program_has_no_route_layer(ring_hlo):
    assert not any(c.startswith("akka.route") for c in layers_of(ring_hlo))


def test_ring_behavior_block_is_named_too(ring_hlo):
    assert "akka.behavior.ring" in layers_of(ring_hlo)


def _messages(m=64, n=16, p=4):
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.integers(0, n, size=m), jnp.int32),
            jnp.asarray(rng.integers(0, 3, size=m), jnp.int32),
            jnp.asarray(rng.integers(0, 5, size=(m, p)), jnp.float32),
            jnp.ones((m,), jnp.bool_), n)


def _blocks(fn, *args) -> set:
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    return set(re.findall(r"akka\.deliver\.(\w+)", text))


@pytest.mark.parametrize("mode, blocks", [
    ("scatter", {"scatter", "max"}),
    ("merge", {"merge_sort", "prefix", "compact", "diffs", "expand", "max"}),
])
def test_each_reduce_kernel_opens_its_blocks(mode, blocks):
    dst, _, payload, valid, n = _messages()
    got = _blocks(lambda d, pl, v: sg.deliver(d, pl, v, n, need_max=True,
                                              mode=mode),
                  dst, payload, valid)
    assert got == blocks


@pytest.mark.parametrize("platform, m, kernel", [
    ("cpu", 1 << 20, "scatter"),
    ("tpu", sg.SCATTER_MAX_M, "scatter"),
    ("tpu", sg.SCATTER_MAX_M + 1, "merge"),
    ("tpu", 1 << 20, "merge"),
])
def test_auto_resolves_to_scatter_or_merge(monkeypatch, platform, m, kernel):
    """The one decision: scatter on a CPU and up to SCATTER_MAX_M rows,
    the wide merge above that elsewhere — and the program `auto` traces
    is that kernel's, block for block."""
    n = 4096
    assert sg.choose_reduce_kernel(m, n, 4, platform) == kernel
    monkeypatch.setattr(sg, "_resolve_platform", lambda x: platform)
    args = (jax.ShapeDtypeStruct((m,), jnp.int32),
            jax.ShapeDtypeStruct((m, 4), jnp.float32),
            jax.ShapeDtypeStruct((m,), jnp.bool_))
    auto, forced = (_blocks(lambda d, pl, v: sg.deliver(d, pl, v, n,
                                                        mode=mode), *args)
                    for mode in ("auto", kernel))
    assert auto == forced
    assert ("scatter" in auto) == (kernel == "scatter")


_WIDE_SLOTS = {"sort", "rank", "place", "spill", "reduce",
               "prefix", "compact", "diffs", "expand"}


@pytest.mark.parametrize("backend, cap, flagged, blocks", [
    ("xla", 8, False, {"rank", "place", "spill", "reduce"}),
    ("xla", 8, True, {"rank", "place", "spill", "reduce"}),
    # the wide family's aggregation reads the rows `sort` left: no
    # `merge_sort` of its own
    ("reference", 8, False, _WIDE_SLOTS),
    # the recipients' flags travel only where they are given and a spill
    # region can hold what they retain (PR 37)
    ("reference", 8, True, _WIDE_SLOTS | {"kind"}),
    ("reference", 0, True, _WIDE_SLOTS),
])
def test_each_slots_kernel_opens_its_blocks(backend, cap, flagged, blocks):
    dst, mtype, payload, valid, n = _messages()
    kind = jnp.arange(n) % 2 == 0 if flagged else None
    got = _blocks(lambda d, t, pl, v: sg.deliver_slots(
        d, t, pl, v, n, 2, spill_cap=cap, slots_kind=kind, backend=backend),
        dst, mtype, payload, valid)
    assert got == blocks


def test_static_delivery_opens_its_block():
    n = 16
    topo = sg.StaticTopology.from_dst_table(
        ((np.arange(n) + 1) % n)[:, None])
    got = _blocks(lambda pl, v: sg.deliver_static(topo, (), pl, v),
                  jnp.ones((n, 4), jnp.float32), jnp.ones((n,), jnp.bool_))
    assert got == {"static"}


def _host_events(tmp_path, system):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        step0 = system._host_step
        system.run(2)
        system.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    events = [e for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    return step0, events


@pytest.mark.parametrize("which", ["ring", "mesh_ring"])
def test_run_leaves_a_host_span_with_the_steps_it_launched(
        which, tmp_path, request):
    system = request.getfixturevalue(which)
    system.run(2)  # warm: the traced call below compiles nothing
    system.block_until_ready()
    step0, events = _host_events(tmp_path, system)
    spans = [e for e in events if e.name == "akka.device.run[2]"]
    assert len(spans) == 1
    stats = dict(spans[0].stats)
    assert stats["step0"] == step0 and stats["steps"] == 2
    assert spans[0].duration_ns > 0
