"""Unified telemetry plane (ISSUE 7): device metric slab bit-parity against
the numpy oracle under the murmur3 chaos harness, host MetricsRegistry
(series, collectors, exposition, sinks), snapshot schema v3, the
pipeline_stats percentile fix, the derived flight-recorder field map, and
the decode_attention legacy-layout upgrade path.

The slab assertions are EXACT (array_equal on int counts): bucketing is
integer arithmetic shared between the jitted accumulator and the *_np
twins, so any drift between a run and its oracle replay is a bug, not
noise — the testkit/chaos.py parity discipline applied to telemetry.
"""

import inspect
import json
import math
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from akka_tpu.actor.supervision import Directive
from akka_tpu.batched import Emit, LaneSupervisor, behavior
from akka_tpu.batched.core import BatchedSystem
from akka_tpu.batched.metrics_slab import (ASK_ARM_COL, BOUNDARIES,
                                           HIST_ASK, HIST_NAMES,
                                           HIST_OCCUPANCY, HIST_RETRY,
                                           HIST_SOJOURN, N_BUCKETS, N_HIST,
                                           bucket_label, bucket_of,
                                           bucket_of_np,
                                           bucket_upper_bounds, masked_hist,
                                           masked_hist_np, slab_totals)
from akka_tpu.batched.sharded import ShardedBatchedSystem
from akka_tpu.config import Config
from akka_tpu.event.metrics import (MetricsRegistry, _host_bucket,
                                    from_config)
from akka_tpu.testkit import chaos

P = 4  # payload width used throughout

EMIT_SALT, LATCH_SALT, TELL_SALT, DST_SALT = 7, 11, 12, 13


# ------------------------------------------------------------ bucket parity
def test_bucket_of_matches_numpy_twin():
    v = np.concatenate([np.arange(-4, 70), 2 ** np.arange(15),
                        2 ** np.arange(15) - 1, [10 ** 6]]).astype(np.int32)
    dev = np.asarray(bucket_of(jnp.asarray(v)))
    np.testing.assert_array_equal(dev, bucket_of_np(v))
    # boundary semantics: 0 -> bucket 0, 1 -> bucket 1, 2^k -> bucket k+1,
    # saturation into the last bucket
    assert bucket_of_np(np.asarray([0]))[0] == 0
    assert bucket_of_np(np.asarray([1]))[0] == 1
    assert bucket_of_np(np.asarray([BOUNDARIES[-1]]))[0] == N_BUCKETS - 1
    assert bucket_of_np(np.asarray([10 ** 9]))[0] == N_BUCKETS - 1


def test_masked_hist_matches_numpy_twin_including_all_invalid():
    rng = np.random.default_rng(5)
    v = rng.integers(0, 1 << 15, size=257).astype(np.int32)
    mask = rng.random(257) < 0.4
    dev = np.asarray(masked_hist(jnp.asarray(v), jnp.asarray(mask)))
    np.testing.assert_array_equal(dev, masked_hist_np(v, mask))
    assert dev.sum() == mask.sum()
    # all-invalid rows: a ZERO histogram, not a bucket-0 spike (the
    # sacrificial-bucket contract)
    none = np.zeros(257, bool)
    dev0 = np.asarray(masked_hist(jnp.asarray(v), jnp.asarray(none)))
    np.testing.assert_array_equal(dev0, np.zeros(N_BUCKETS, np.int64))
    np.testing.assert_array_equal(masked_hist_np(v, none),
                                  np.zeros(N_BUCKETS, np.int64))


def test_bucket_labels_and_upper_bounds():
    assert bucket_label(0) == "0"
    assert bucket_label(1) == "1"
    assert bucket_label(3) == "4-7"
    assert bucket_label(N_BUCKETS - 1) == f">={BOUNDARIES[-1]}"
    ubs = bucket_upper_bounds()
    assert len(ubs) == N_BUCKETS
    assert ubs[0] == 0 and ubs[1] == 1 and ubs[2] == 3
    assert math.isinf(ubs[-1])


# ------------------------------------------------- chaos oracle (tentpole)
def make_chaotic(seed):
    """Supervised accumulator generating all four distributions: chaos-
    scheduled emissions (occupancy + sojourn traffic), chaos crashes via
    inject() (retry depth), and a chaos-flipped latch column (ask lane)."""

    @behavior("chaotic", {"acc": ((), jnp.float32), "rep": ((), jnp.int32)},
              always_on=True,
              supervisor=LaneSupervisor(directive=Directive.RESTART))
    def chaotic(state, inbox, ctx):
        n = ctx.n_actors
        hit = chaos.chaos_hit(seed, ctx.step, ctx.actor_id, 0.3, EMIT_SALT)
        flip = chaos.chaos_hit(seed, ctx.step, ctx.actor_id, 0.05,
                               LATCH_SALT)
        rep = jnp.where(flip, 1, state["rep"]).astype(jnp.int32)
        dst = (ctx.actor_id * 5 + 3) % n
        return ({"acc": state["acc"] + inbox.count.astype(jnp.float32),
                 "rep": rep},
                Emit.single(dst, jnp.zeros((P,)), 1, P, when=hit))

    return chaos.inject(chaotic, seed=seed, crash_rate=0.08)


def _read_pre(sys, n):
    return {
        "retries": np.asarray(jax.device_get(sys.state["_retries"])),
        "rep": np.asarray(jax.device_get(sys.state["rep"])),
        "arm": np.asarray(jax.device_get(sys.state[ASK_ARM_COL])),
        "alive": np.asarray(jax.device_get(sys.alive)),
        "dst": np.asarray(jax.device_get(sys.inbox_dst)),
        "valid": np.asarray(jax.device_get(sys.inbox_valid)),
        "enq": np.asarray(jax.device_get(sys.inbox_enq)),
        "step": int(np.asarray(jax.device_get(sys.step_count))),
    }


def _oracle_delta(pre, post, n):
    """Numpy replay of one accumulate_step call from observed pre/post
    device state — the host-side twin of metrics_slab.accumulate_step."""
    exp = np.zeros((N_HIST, N_BUCKETS), np.int64)
    valid = pre["valid"].astype(bool)
    retry_mask = post["retries"] > pre["retries"]
    newly = (post["rep"] != 0) & (pre["rep"] == 0)
    busy = valid.any() or retry_mask.any() or newly.any()
    if not busy:
        return exp, False
    dst = pre["dst"]
    routable = valid & (dst >= 0) & (dst < n)
    dcount = np.bincount(dst[routable].astype(np.int64), minlength=n)[:n]
    exp[HIST_OCCUPANCY] = masked_hist_np(dcount, pre["alive"])
    exp[HIST_SOJOURN] = masked_hist_np(
        np.maximum(pre["step"] - pre["enq"], 0), valid)
    exp[HIST_RETRY] = masked_hist_np(post["retries"], retry_mask)
    exp[HIST_ASK] = masked_hist_np(
        np.maximum(pre["step"] + 1 - pre["arm"], 0), newly)
    return exp, True


@pytest.mark.parametrize("backend", [None, "reference"],
                         ids=["auto", "reference"])
def test_slab_bit_parity_chaos_oracle(backend):
    """Every histogram lane bit-identical to the numpy oracle, per step,
    under chaos crashes + chaos traffic, on both delivery backends."""
    seed, n, steps = 17, 48, 30
    sys = BatchedSystem(n, [make_chaotic(seed)], payload_width=P,
                        host_inbox=64, delivery_backend=backend,
                        attention_latch_col="rep", metrics_enabled=True)
    sys.spawn_block(0, n)
    # arm stamps as the bridge would: a spread of past dispatch counters
    sys.state[ASK_ARM_COL] = jnp.asarray(np.arange(n) % 5, jnp.int32)

    expected = np.zeros((N_HIST, N_BUCKETS), np.int64)
    saw_quiet = saw_busy = False
    for t in range(steps):
        if chaos.chaos_hit_np(seed, t, np.asarray([0]), 0.5, TELL_SALT)[0]:
            k = 1 + int(chaos.chaos_hash(seed, t, 1, TELL_SALT)) % 5
            dsts = np.asarray(
                [int(chaos.chaos_hash(seed, t, j, DST_SALT)) % n
                 for j in range(k)], np.int32)
            sys.tell(dsts, np.ones((k, P), np.float32))
        sys._flush_staged()
        pre = _read_pre(sys, n)
        sys.run(1)
        post = {"retries": np.asarray(jax.device_get(sys.state["_retries"])),
                "rep": np.asarray(jax.device_get(sys.state["rep"]))}
        delta, busy = _oracle_delta(pre, post, n)
        expected += delta
        saw_busy |= busy
        saw_quiet |= not busy
        np.testing.assert_array_equal(slab_totals(sys.metrics), expected,
                                      err_msg=f"slab diverged at step {t}")
    # the run must actually have exercised what it claims to test
    assert saw_busy
    assert expected[HIST_OCCUPANCY].sum() > 0
    assert expected[HIST_SOJOURN].sum() > 0
    assert expected[HIST_RETRY].sum() > 0, "chaos crashes produced no retry"
    assert expected[HIST_ASK].sum() > 0, "no latch flip hit the ask lane"
    # epoch word == slab running sum; drain returns once, then gates
    assert sys.metrics_epoch_value() == int(expected.sum())
    drained = sys.drain_metrics()
    assert drained is not None
    step, lanes = drained
    assert step == steps
    assert set(lanes) == set(HIST_NAMES)
    np.testing.assert_array_equal(lanes["mailbox_occupancy"],
                                  expected[HIST_OCCUPANCY])
    assert sys.drain_metrics() is None  # epoch unchanged -> gated


@pytest.mark.parametrize("backend", [None, "reference"],
                         ids=["auto", "reference"])
def test_slab_empty_window_stays_zero(backend):
    """A metrics-enabled system with no traffic accumulates NOTHING: the
    quiet predicate gates the whole pass, the epoch stays 0, and the
    drain stays gated."""

    @behavior("idle", {"acc": ((), jnp.float32)})
    def idle(state, inbox, ctx):
        return {"acc": state["acc"]}, Emit.none(1, P)

    sys = BatchedSystem(32, [idle], payload_width=P,
                        delivery_backend=backend, metrics_enabled=True)
    sys.spawn_block(0, 32)
    sys.run(10)
    np.testing.assert_array_equal(slab_totals(sys.metrics),
                                  np.zeros((N_HIST, N_BUCKETS), np.int64))
    assert sys.metrics_epoch_value() == 0
    assert sys.drain_metrics() is None


def test_metrics_off_allocates_nothing():
    @behavior("idle2", {"acc": ((), jnp.float32)})
    def idle(state, inbox, ctx):
        return {"acc": state["acc"]}, Emit.none(1, P)

    sys = BatchedSystem(16, [idle], payload_width=P)
    assert not sys.metrics_on
    assert sys.inbox_enq.shape == (0,)
    assert ASK_ARM_COL not in sys.state
    sys.spawn_block(0, 16)
    sys.tell(0, np.ones(P, np.float32))
    sys.run(3)
    assert sys.metrics_epoch_value() == 0
    assert sys.drain_metrics() is None


# --------------------------------------------------------- sharded parity
def test_sharded_slab_exact_ring_counts():
    """8-shard ring: exactly one message in flight, so every lane total is
    predictable in closed form — occupancy samples only the BUSY shard's
    alive block, sojourn ages are 0 (host flush) then 1 (emission)."""
    assert jax.device_count() >= 8

    @behavior("mring", {"seen": ((), jnp.float32)})
    def mring(state, inbox, ctx):
        nxt = (ctx.actor_id + 1) % ctx.n_actors
        return ({"seen": state["seen"] + inbox.count.astype(jnp.float32)},
                Emit.single(nxt, jnp.zeros((P,)), 1, P,
                            when=inbox.count > 0))

    n, n_dev, steps = 32, 8, 24
    m = n // n_dev  # lanes per shard
    sys = ShardedBatchedSystem(capacity=n, behaviors=[mring],
                               n_devices=n_dev, payload_width=P,
                               metrics_enabled=True)
    sys.spawn_block(mring, n)
    sys.tell(0, np.zeros(P, np.float32))
    sys.run(steps)

    totals = slab_totals(sys.metrics)
    expected = np.zeros((N_HIST, N_BUCKETS), np.int64)
    # each step exactly one shard is busy: its receiving lane counts 1
    # message (bucket 1), the other m-1 alive lanes count 0 (bucket 0)
    expected[HIST_OCCUPANCY, 0] = steps * (m - 1)
    expected[HIST_OCCUPANCY, 1] = steps
    # the initial host tell is stamped by its flushing dispatch and
    # delivered the same step (age 0); every hop after is emitted at step
    # t and delivered at t+1 (age 1)
    expected[HIST_SOJOURN, 0] = 1
    expected[HIST_SOJOURN, 1] = steps - 1
    np.testing.assert_array_equal(totals, expected)
    assert sys.metrics_epoch_value() == int(expected.sum())
    drained = sys.drain_metrics()
    assert drained is not None and drained[0] == steps
    assert sys.drain_metrics() is None


# -------------------------------------------------- snapshot schema v3
def _traffic_system(metrics=True, n=24):
    @behavior("snap", {"acc": ((), jnp.float32)}, always_on=True)
    def snap(state, inbox, ctx):
        nxt = (ctx.actor_id + 1) % ctx.n_actors
        return ({"acc": state["acc"] + 1.0},
                Emit.single(nxt, jnp.zeros((P,)), 1, P,
                            when=inbox.count > 0))

    sys = BatchedSystem(n, [snap], payload_width=P, metrics_enabled=metrics)
    sys.spawn_block(0, n)
    return sys


def test_snapshot_v3_roundtrips_metrics_slab(tmp_path):
    from akka_tpu.persistence.slab_snapshot import (SCHEMA_VERSION,
                                                    save_slabs,
                                                    slab_pytree)
    assert SCHEMA_VERSION == 3
    src = _traffic_system()
    src.tell(0, np.zeros(P, np.float32))
    src.run(6)
    tree = slab_pytree(src)
    assert int(tree["schema_version"]) == 3
    assert "metrics" in tree and "inbox_enq" in tree
    path = save_slabs(src, str(tmp_path))

    dst = _traffic_system()
    dst.restore(path)
    np.testing.assert_array_equal(slab_totals(dst.metrics),
                                  slab_totals(src.metrics))
    np.testing.assert_array_equal(np.asarray(jax.device_get(dst.inbox_enq)),
                                  np.asarray(jax.device_get(src.inbox_enq)))
    # restore resets the drain gate: the restored slab is drainable once
    drained = dst.drain_metrics()
    assert drained is not None and drained[0] == 6


def test_snapshot_v2_zero_fills_telemetry_slabs(tmp_path):
    """A pre-telemetry (v2) snapshot restores with the metric slab and enq
    column ZEROED — never the target's stale pre-restore values."""
    from akka_tpu.persistence.slab_snapshot import (restore_slab_pytree,
                                                    slab_pytree)
    src = _traffic_system()
    src.tell(0, np.zeros(P, np.float32))
    src.run(4)
    tree = slab_pytree(src)
    del tree["metrics"], tree["inbox_enq"]
    tree["schema_version"] = np.int64(2)

    dst = _traffic_system()
    dst.tell(3, np.zeros(P, np.float32))
    dst.run(3)  # pollute the target's slab
    assert slab_totals(dst.metrics).sum() > 0
    restore_slab_pytree(dst, tree)
    np.testing.assert_array_equal(slab_totals(dst.metrics),
                                  np.zeros((N_HIST, N_BUCKETS), np.int64))
    np.testing.assert_array_equal(np.asarray(jax.device_get(dst.inbox_enq)),
                                  np.zeros_like(
                                      np.asarray(
                                          jax.device_get(dst.inbox_enq))))


def test_snapshot_metrics_shape_mismatch_zero_fills(tmp_path):
    """v3 snapshot from a metrics-ON system restores into a metrics-OFF
    target: the telemetry slabs shape-mismatch and zero-fill instead of
    failing the restore (attention-word precedent)."""
    from akka_tpu.persistence.slab_snapshot import (restore_slab_pytree,
                                                    slab_pytree)
    src = _traffic_system(metrics=True)
    src.tell(0, np.zeros(P, np.float32))
    src.run(4)
    dst = _traffic_system(metrics=False)
    restore_slab_pytree(dst, slab_pytree(src))  # must not raise
    np.testing.assert_array_equal(dst.read_state("acc"),
                                  src.read_state("acc"))


def test_snapshot_newer_schema_rejected():
    from akka_tpu.persistence.slab_snapshot import (SCHEMA_VERSION,
                                                    restore_slab_pytree,
                                                    slab_pytree)
    src = _traffic_system()
    tree = slab_pytree(src)
    tree["schema_version"] = np.int64(SCHEMA_VERSION + 1)
    with pytest.raises(ValueError, match="newer"):
        restore_slab_pytree(_traffic_system(), tree)


# --------------------------------------------------------- host registry
def test_registry_counter_gauge_and_step_stamp():
    reg = MetricsRegistry()
    reg.counter("tells").inc(3, step=7)
    reg.gauge("depth").set(2.5, step=9)
    assert reg.counter("tells").value == 3
    assert reg.gauge("depth").value == 2.5
    # step stamps ride per series; the registry's correlation axis only
    # advances monotonically via set_step / slab ingestion
    assert reg.counter("tells").step == 7
    assert reg.gauge("depth").step == 9
    reg.set_step(4)
    assert reg.step == 4
    reg.set_step(2)
    assert reg.step == 4  # monotonic


def test_host_histogram_nearest_rank_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    # two samples: p50 must be the FIRST (rank ceil(0.5*2) = 1), i.e. the
    # bucket of 1 -> upper bound 1; the pre-fix rule indexed one past
    h.observe(1)
    h.observe(16)
    assert h.percentile(0.50) == 1.0
    assert h.percentile(0.99) == 31.0  # bucket of 16 -> [16, 31]
    assert _host_bucket(0) == 0 and _host_bucket(1) == 1
    assert _host_bucket(2 ** 70) == 63  # saturates
    s = h.snapshot()
    assert s["count"] == 2 and s["sum"] == 17.0


def test_registry_collector_pull_skips_non_numeric():
    reg = MetricsRegistry()
    reg.register_collector("pipe", lambda: {"steps": 5, "ok": True,
                                            "name": "x", "depth": 2.0})
    reg.register_collector("sick", lambda: 1 / 0)
    text = reg.expose()
    assert "akka_pipe_steps 5" in text
    assert "akka_pipe_depth 2" in text
    assert "akka_pipe_ok" not in text  # bools skipped
    assert "akka_pipe_name" not in text
    assert "sick" not in text  # a raising collector never breaks expose


def test_registry_ingests_device_slab_and_exposes_prometheus():
    reg = MetricsRegistry()
    lanes = {name: np.zeros(N_BUCKETS, np.int64) for name in HIST_NAMES}
    lanes["mailbox_occupancy"][0] = 10
    lanes["mailbox_occupancy"][1] = 4
    reg.ingest_device_slab(lanes, step=42)
    h = reg.device_histogram("mailbox_occupancy")
    assert h is not None and h.count == 14 and h.step == 42
    assert h.percentile(0.50) == 0.0  # rank 7 of 14 in bucket 0
    assert h.percentile(0.99) == 1.0
    text = reg.expose()
    assert 'akka_device_mailbox_occupancy_bucket{le="0"} 10' in text
    assert 'akka_device_mailbox_occupancy_bucket{le="1"} 14' in text
    assert 'le="+Inf"' in text  # saturating bucket label
    assert "akka_device_mailbox_occupancy_count 14" in text
    assert "akka_device_mailbox_occupancy_step 42" in text
    assert reg.step == 42
    # cumulative replace: a later drain overwrites, not adds
    lanes["mailbox_occupancy"][1] = 6
    reg.ingest_device_slab(lanes, step=50)
    assert reg.device_histogram("mailbox_occupancy").count == 16
    snap = reg.snapshot()
    assert snap["device"]["device_mailbox_occupancy"]["step"] == 50


def test_registry_http_endpoint(tmp_path):
    reg = MetricsRegistry()
    reg.counter("hits").inc(7)
    port = reg.serve_http(0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as resp:
            body = resp.read().decode()
        assert "akka_hits 7" in body
    finally:
        reg.close()


def test_registry_jsonl_sink(tmp_path):
    reg = MetricsRegistry()
    reg.counter("frames").inc(2, step=3)
    path = tmp_path / "m" / "metrics.jsonl"
    reg.start_jsonl(str(path), interval_s=30.0)
    reg.emit_jsonl_once()
    reg.close()  # writes one final frame
    rows = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(rows) >= 2
    assert all(r["event"] == "metrics" and "ts" in r for r in rows)
    assert rows[-1]["counters"]["frames"] == 2


def test_from_config_gating(tmp_path):
    assert from_config(None) is None
    assert from_config(Config({"akka": {"metrics": {"enabled": False}}})) \
        is None
    reg = from_config(Config({"akka": {"metrics": {
        "enabled": True, "namespace": "tpu",
        "jsonl-path": str(tmp_path / "m.jsonl"),
        "jsonl-interval": "10s"}}}))
    try:
        assert reg is not None and reg.namespace == "tpu"
        assert reg._jsonl_fh is not None
    finally:
        reg.close()


# ------------------------------------------- pipeline_stats pct fix (sat 1)
def _stuff_dispatches(h, micros):
    """The runtime's host side with these dispatch durations logged (the
    one timing of a dispatch since ISSUE 36: batched/host_side.py)."""
    host = h.runtime._host
    host._dispatch_ns.extend(int(us * 1000) for us in micros)
    host.dispatches += len(micros)
    return host


def test_pipeline_stats_nearest_rank_and_cached_sort():
    from akka_tpu.batched.bridge import BatchedRuntimeHandle
    h = BatchedRuntimeHandle(capacity=64, payload_width=P, host_inbox=64,
                             promise_rows=8)
    try:
        assert h.pipeline_stats()["dispatch_p50_us"] == 0.0  # no runtime yet
        host = _stuff_dispatches(h, range(1, 101))  # 1..100 us
        st = h.pipeline_stats()
        # nearest rank: p50 of 100 samples is the 50th (50us), not the
        # 51st the old min(int(q*n), n-1) picked; p99 is the 99th
        assert st["dispatch_p50_us"] == 50.0
        assert st["dispatch_p99_us"] == 99.0
        assert h.host_stats()["dispatch_us_p50"] == 50.0
        # cached sorted snapshot: mutating the deque WITHOUT a new dispatch
        # counted must serve the cached percentiles...
        host._dispatch_ns.clear()
        assert h.pipeline_stats()["dispatch_p50_us"] == 50.0
        # ...and a dispatch invalidates
        _stuff_dispatches(h, [7])
        assert h.pipeline_stats()["dispatch_p50_us"] == 7.0
    finally:
        h.shutdown()


def test_pipeline_stats_two_sample_median():
    from akka_tpu.batched.bridge import BatchedRuntimeHandle
    h = BatchedRuntimeHandle(capacity=64, payload_width=P, host_inbox=64,
                             promise_rows=8)
    try:
        _stuff_dispatches(h, [1, 100])
        # the regression this satellite fixes: p50 of [1, 100] was 100
        assert h.pipeline_stats()["dispatch_p50_us"] == 1.0
        assert h.pipeline_stats()["dispatch_p99_us"] == 100.0
    finally:
        h.shutdown()


# -------------------------------- flight recorder derived _FIELDS (sat 2)
def test_flight_recorder_fields_derived_from_spi():
    from akka_tpu.event.flight_recorder import (FlightRecorder,
                                                InMemoryFlightRecorder,
                                                _NON_HOOKS)
    derived = InMemoryFlightRecorder._FIELDS
    spi = {name: fn for name, fn in vars(FlightRecorder).items()
           if callable(fn) and not name.startswith("_")
           and name not in _NON_HOOKS}
    # every SPI hook appears, with exactly its signature's field names
    assert set(derived) == set(spi)
    for name, fn in spi.items():
        params = tuple(inspect.signature(fn).parameters)[1:]
        assert derived[name] == params, name
    # structured hooks actually record under those names
    r = InMemoryFlightRecorder()
    r.device_supervision("s", 1, 2, 3, 4, 5, 6, 7)
    ev = r.events()[0]
    assert ev["event"] == "device_supervision"
    assert (ev["steps"], ev["failed"], ev["dead_letters"]) == (1, 2, 7)


# ------------------------------ decode_attention legacy 4-word path (sat 3)
def test_decode_attention_legacy_four_word_upgrade():
    from akka_tpu.batched.supervision import (ATT_FAILED_BIT, ATT_LATCH_BIT,
                                              decode_attention)
    legacy = np.asarray([ATT_FAILED_BIT | ATT_LATCH_BIT, 11, 3, 42],
                        np.int32)
    d = decode_attention(legacy)
    assert d["any_failed"] and d["any_latched"] and not d["any_escalated"]
    assert d["mail_dropped"] == 11
    assert d["dead_letters"] == 3
    assert d["step"] == 42
    # new lanes zero-fill; the progress heartbeat aliases the legacy step
    assert d["exchange_dropped"] == 0
    np.testing.assert_array_equal(d["progress_per_shard"], [42])
    # sharded legacy block: flags OR, counters sum, step max
    block = np.asarray([[ATT_FAILED_BIT, 1, 0, 10],
                        [0, 2, 5, 12]], np.int32)
    d2 = decode_attention(block)
    assert d2["any_failed"] and d2["mail_dropped"] == 3
    assert d2["dead_letters"] == 5 and d2["step"] == 12
    np.testing.assert_array_equal(d2["progress_per_shard"], [10, 12])
