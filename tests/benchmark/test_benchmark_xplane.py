"""The reduction from a device trace to numbers, on a small trace recorded on
a TPU v5e (benchmark/tools/record_fixture.py: three 4-step chunks of a
4,096-actor dynamic ring) and on hand-made events."""

import gzip
import os

import pytest

from benchmark import xplane
from benchmark.xplane import DeviceLine, Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(os.path.join(DATA, "small.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return xplane.load(str(path))


def window(trace):
    mods = trace.devices[0].modules
    return min(e.start for e in mods), max(e.end for e in mods)


def test_recorded_trace_has_one_device_and_three_program_runs(recorded):
    assert len(recorded.devices) == 1
    assert [m.name for m in recorded.devices[0].modules] == ["jit__run_impl"] * 3


def test_recorded_ops_are_named_module_slash_op(recorded):
    names = {e.name for e in recorded.devices[0].ops}
    assert all(n.startswith("jit__run_impl/") for n in names)
    assert any(n.startswith("jit__run_impl/sort.") for n in names)
    assert not any("=" in n or "%" in n for n in names)  # no whole HLO lines


def test_recorded_busy_is_under_the_programs_time_and_idle_share_follows(recorded):
    a, b = window(recorded)
    busy = xplane.device_busy(recorded, a, b)[0]
    in_programs = sum(xplane.module_sums(recorded, a, b).values())
    assert 0.9 * in_programs < busy <= in_programs * 1.0001
    assert xplane.idle_share(recorded, a, b) == pytest.approx(
        100 * (1 - busy / ((b - a) / 1e9)))


def test_recorded_self_times_sum_to_busy_time_counted_once(recorded):
    a, b = window(recorded)
    sums = xplane.op_sums(recorded, a, b)
    assert sum(sums.values()) == pytest.approx(
        xplane.device_busy(recorded, a, b)[0], rel=1e-6)
    top = xplane.top(sums, 2)
    assert {n.split(".")[0] for n, _ in top} == {"jit__run_impl/sort"}


def test_recorded_gaps_go_to_what_the_host_was_doing(recorded):
    a, b = window(recorded)
    gaps = xplane.attribute_gaps(recorded, a, b)
    idle = (b - a) / 1e9 - xplane.device_busy(recorded, a, b)[0]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    # between chunks the host reads the step count back
    assert max(gaps, key=gaps.get) == "np.asarray(jax.Array)"


def test_recorded_marks_are_found(recorded):
    marks = xplane.find_marks(recorded, "bench.chunk.")
    assert sorted(marks) == ["0", "1", "2"]
    assert marks["0"] < marks["1"] < marks["2"]


def handmade():
    ops = [Event("m/while.1", 0, 100), Event("m/a", 10, 40),
           Event("m/b", 40, 90), Event("m/c", 200, 260)]
    dev = DeviceLine("/device:TPU:0", ops=ops,
                     modules=[Event("m", 0, 100), Event("m", 200, 260)])
    return Trace([dev], [])


def test_union_merges_overlaps_and_nesting():
    ev = [Event("x", 0, 10), Event("y", 5, 20), Event("z", 30, 40),
          Event("in", 32, 35)]
    assert xplane.union(ev) == [[0, 20], [30, 40]]
    assert xplane.busy_ns(ev) == 30


def test_busy_idle_and_clipping_by_hand():
    t = handmade()
    assert xplane.device_busy(t, 0, 400) == [160 / 1e9]
    assert xplane.idle_share(t, 0, 400) == pytest.approx(60.0)
    assert xplane.device_busy(t, 50, 230) == [(50 + 30) / 1e9]


def test_self_time_leaves_children_out_of_the_parent():
    sums = xplane.op_sums(handmade(), 0, 400)
    assert sums == pytest.approx({"m/while.1": 20 / 1e9, "m/a": 30 / 1e9,
                                 "m/b": 50 / 1e9, "m/c": 60 / 1e9})


def test_gap_attribution_prefers_the_shortest_cover_and_skips_marks():
    t = handmade()
    t.devices[0].ops = [Event("m/a", 0, 1e6), Event("m/b", 4e6, 5e6),
                        Event("m/c", 5.2e6, 6e6), Event("m/d", 9e6, 10e6)]
    t.host = [Event("bench.window", 0, 10e6), Event("outer", 0.5e6, 9.5e6),
              Event("journal", 1.1e6, 3.9e6), Event("late", 8e6, 9e6)]
    got = xplane.attribute_gaps(t, 0, 10e6)
    assert got["journal"] == pytest.approx(3e6 / 1e9)  # shortest full cover
    assert got["short_gaps"] == pytest.approx(0.2e6 / 1e9)  # under 1 ms
    assert got["outer"] == pytest.approx(3e6 / 1e9)  # `late` covers a third
    assert "bench.window" not in got


def test_gap_with_no_host_event_is_unattributed():
    t = handmade()
    assert xplane.attribute_gaps(t, 0, 5e6) == pytest.approx(
        {"short_gaps": 100 / 1e9, "unattributed": (5e6 - 260) / 1e9})


def test_module_and_op_names():
    assert xplane.module_name("jit_multi_step(7329087)") == "jit_multi_step"
    assert xplane.op_name("%fusion.53 = f32[8]{0} fusion(f32[8]{0} %p)") == "fusion.53"
    assert xplane.op_name("dot_general.1") == "dot_general.1"


def test_fullest_device_decides_on_several_chips():
    t = handmade()
    t.devices.append(DeviceLine("/device:TPU:1", ops=[Event("m/a", 0, 300)],
                                modules=[Event("m", 0, 300)]))
    assert xplane.fullest(t, 0, 400) == 1
    assert xplane.idle_share(t, 0, 400) == pytest.approx(25.0)


@pytest.mark.parametrize("name", ["all_to_all.46", "all-to-all.15",
                                  "all-to-all-start.3", "all_gather.2"])
def test_collective_share_reads_the_names_both_backends_give(name):
    """The TPU names the exchange `all_to_all.46`, XLA:CPU `all-to-all.15`."""
    import json
    import os

    from benchmark.harness import BENCH
    from benchmark.readers import op_share

    with open(os.path.join(BENCH, "metrics", "xshard_collective_share.json")) as f:
        args = json.load(f)["args"]
    ops = [Event(f"jit_multi_step/{name}", 0, 25), Event("jit_multi_step/sort.1", 25, 100)]
    trace = Trace([DeviceLine("/device:TPU:0", ops=ops,
                              modules=[Event("jit_multi_step", 0, 100)])], [])
    obs = {"trace": trace, "trace_a": 0, "trace_b": 200, "trace_fullest": 0}
    assert op_share.read(obs, **args) == pytest.approx(25.0)
    assert op_share.read(obs, pattern="/no-such-op") is None  # never 0
