"""From a trace's stored programs to layers: the wire-format decoder and the
grouping of `benchmark/xscope.py`, and the reader `scope_share`, on the two
recorded traces (`small`: a TPU v5e executable from before the scopes;
`scoped`: the same ring recorded from a tree that has them, both by
benchmark/tools/record_fixture.py), on hand-encoded bytes and on hand-made
events."""

import gzip
import os
import shutil

import pytest

from benchmark import harness, xplane, xscope
from benchmark.readers import scope_share
from benchmark.xplane import DeviceLine, Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RING = ["^jit__run_impl$"]


def recorded(tmp_path_factory, name):
    path = tmp_path_factory.mktemp(name) / f"{name}.xplane.pb"
    with gzip.open(os.path.join(DATA, f"{name}.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    trace = xplane.load(str(path))
    mods = trace.devices[0].modules
    return {"trace": trace, "trace_path": str(path), "trace_fullest": 0,
            "trace_a": min(e.start for e in mods),
            "trace_b": max(e.end for e in mods)}


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return recorded(tmp_path_factory, "small")


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    return recorded(tmp_path_factory, "scoped")


def sums_of(obs):
    scopes = xscope.read_scopes(obs["trace_path"])
    return xscope.scope_sums(obs["trace"], scopes, obs["trace_a"],
                             obs["trace_b"])


# ------------------------------------------ the pre-scope executable (small)
def test_decoder_reads_the_name_stack_of_the_old_traces_sorts(small):
    scopes = xscope.read_scopes(small["trace_path"])
    for op in ("sort.181", "sort.188"):
        assert scopes[f"jit__run_impl/{op}"] == \
            "jit(_run_impl)/while/body/closed_call/sort"
    assert "jit__run_impl/reduce-window.90" not in scopes  # no op_name


def test_old_trace_reads_unscoped_and_sums_to_busy_time_once(small):
    sums = sums_of(small)
    assert set(sums) == {(xscope.UNSCOPED, xscope.UNSCOPED)}
    total = sum(v for ops in sums.values() for v in ops.values())
    assert total == pytest.approx(sum(xplane.op_sums(
        small["trace"], small["trace_a"], small["trace_b"]).values()))


def test_reader_names_the_compile_cache_for_a_pre_scope_executable(small):
    with pytest.raises(harness.BenchError) as e:
        scope_share.read(dict(small), RING, "akka.deliver")
    assert "older than the scopes" in str(e.value)
    assert harness.compile_cache_dir() in str(e.value)


# ------------------------------------------------ the scoped trace (scoped)
def test_scoped_trace_has_its_sorts_under_the_merge_deliverys_blocks(scoped):
    scopes = xscope.read_scopes(scoped["trace_path"])
    ran = {e.name for e in scoped["trace"].devices[0].ops
           if e.name.startswith("jit__run_impl/sort.")}
    assert {op: xscope.layer_of(scopes[op]) for op in ran} == {
        "jit__run_impl/sort.181": ("akka.deliver", "akka.deliver.merge_sort"),
        "jit__run_impl/sort.188": ("akka.deliver",
                                   "akka.deliver.marker_sort")}


def test_scoped_shares_add_up_to_the_step_programs_time_and_deliver_leads(
        scoped, capsys):
    obs = dict(scoped)
    layers = ["akka.deliver", "akka.behavior", "akka.supervision",
              "akka.emit", "akka.exchange", "akka.metrics", "akka.attention",
              None]
    shares = {s: scope_share.read(obs, RING, s) for s in layers}
    # one program ran in the traced stretch: its layers are all of the
    # busy time, counted once
    assert sum(shares.values()) == pytest.approx(100.0)
    assert max(shares, key=shares.get) == "akka.deliver"
    assert shares["akka.deliver"] > 50 and shares["akka.exchange"] == 0
    assert 0 < shares[None] < 50
    # the table goes to stderr once a run, not once a metric
    err = capsys.readouterr().err
    assert err.count("step program by layer") == 1
    assert "akka.deliver.merge_sort" in err and "jit__run_impl/sort." in err


def test_scoped_grouped_sums_equal_op_sums_total(scoped):
    total = sum(v for ops in sums_of(scoped).values() for v in ops.values())
    assert total == pytest.approx(sum(xplane.op_sums(
        scoped["trace"], scoped["trace_a"], scoped["trace_b"]).values()))


def test_no_step_program_in_the_trace_is_nothing_to_read(scoped):
    assert scope_share.read(dict(scoped), ["^jit_multi_step$"],
                            "akka.deliver") is None


# ------------------------------------------------------- hand-made cases
@pytest.mark.parametrize("path, want", [
    ("jit(f)/while/body/akka.deliver/akka.deliver.sort/sort",
     ("akka.deliver", "akka.deliver.sort")),
    ("jit(f)/akka.deliver/sort", ("akka.deliver", "akka.deliver")),
    ("jit(multi_step)/while/body/closed_call/shard_map/akka.behavior/"
     "vmap(jit(_where))/cond/branch_1_fun/select_n",
     ("akka.behavior", "akka.behavior")),
    ("jit(f)/akka.exchange/jit(_take)/akka.exchange.bucket/vmap(gather)",
     ("akka.exchange", "akka.exchange.bucket")),
    ("jit(f)/while/body/closed_call/sort", ("unscoped", "unscoped")),
    ("", ("unscoped", "unscoped")),
    (None, ("unscoped", "unscoped")),
])
def test_layer_is_the_first_and_block_the_deepest_akka_component(path, want):
    assert xscope.layer_of(path) == want


def test_self_time_is_grouped_by_layer_and_block():
    ops = [Event("m/while.1", 0, 100), Event("m/sort.1", 10, 40),
           Event("m/fusion.2", 40, 90), Event("n/copy.3", 200, 260)]
    trace = Trace([DeviceLine("/device:TPU:0", ops=ops, modules=[
        Event("m", 0, 100), Event("n", 200, 260)])], [])
    scopes = {"m/sort.1": "jit(f)/while/body/akka.deliver/akka.deliver.sort/s",
              "m/fusion.2": "jit(f)/while/body/akka.deliver/mul",
              "m/while.1": "jit(f)/while"}
    sums = xscope.scope_sums(trace, scopes, 0, 300)
    assert sums == {
        ("akka.deliver", "akka.deliver.sort"): {"m/sort.1": 30e-9},
        ("akka.deliver", "akka.deliver"): {"m/fusion.2": 50e-9},
        ("unscoped", "unscoped"): {"m/while.1": 20e-9, "n/copy.3": 60e-9}}


# hand-encoded protobuf: just enough of the wire format to write what the
# decoder reads
def varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def hlo_proto(instructions):
    body = b"".join(field(2, field(1, name) + (field(7, field(2, op_name))
                                               if op_name else b""))
                    for name, op_name in instructions)
    return field(1, field(3, field(1, "main") + body))


def space_of(programs):
    """An XSpace with a device plane (ignored) and a `/host:metadata` plane
    holding `programs`: [(event metadata name, instructions)]."""
    plane = field(2, "/host:metadata") + field(5, field(1, 1) + field(
        2, field(1, 1) + field(2, "Hlo Proto")))
    for i, (name, instructions) in enumerate(programs):
        stat = field(1, 1) + field(6, hlo_proto(instructions))
        plane += field(4, field(1, i) + field(2, field(1, i) + field(2, name)
                                              + field(5, stat)))
    return field(1, field(2, "/device:TPU:0")) + field(1, plane)


def test_decoder_keys_instructions_by_module_and_drops_a_clash(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(space_of([
        ("jit_step(11)", [("fusion.1", "jit(step)/akka.deliver/add"),
                          ("sort.2", "jit(step)/akka.deliver/sort"),
                          ("copy.3", "")]),
        ("jit_step(12)", [("fusion.1", "jit(step)/akka.emit/add"),
                          ("sort.2", "jit(step)/akka.deliver/sort")]),
        ("jit_other(13)", [("fusion.1", "jit(other)/mul")]),
    ]))
    assert xscope.read_scopes(str(path)) == {
        "jit_step/sort.2": "jit(step)/akka.deliver/sort",
        "jit_other/fusion.1": "jit(other)/mul"}


# ---------------------------------------------------- finding the file
@pytest.fixture(scope="module")
def tmp_root(tmp_path_factory):
    """A temporary directory as the harness leaves it during a traced run:
    `bench_*/trace/...xplane.pb` with the benchmark's marks (a CPU trace:
    marks are host events)."""
    root = tmp_path_factory.mktemp("tmp_root")
    trace = harness.DeviceTrace(str(root / "bench_one"))
    trace.start()
    trace.stop()
    mark = xplane.find_marks(xplane.load(trace.path))["a"]
    return root, mark


def test_the_runs_trace_file_is_found_by_its_mark(tmp_root):
    root, mark = tmp_root
    found = xscope.find_trace_file(mark, str(root))
    assert found.startswith(str(root / "bench_one")) and \
        found.endswith(".xplane.pb")


def test_no_file_with_the_mark_is_an_error(tmp_root):
    root, mark = tmp_root
    with pytest.raises(harness.BenchError, match="0 trace files"):
        xscope.find_trace_file(mark + 1.0, str(root))


def test_two_files_with_the_mark_are_an_error(tmp_root, tmp_path):
    root, mark = tmp_root
    for name in ("bench_one", "bench_two"):
        shutil.copytree(root / "bench_one", tmp_path / name)
    with pytest.raises(harness.BenchError, match="2 trace files"):
        xscope.find_trace_file(mark, str(tmp_path))
