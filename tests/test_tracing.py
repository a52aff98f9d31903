"""Causal tracing (event/tracing.py + tools/trace_export.py, ISSUE 12):
deterministic head sampling, span trees that survive the AskBatcher
thread hop and the caller-thread columnar wave path, wave_id agreement
between spans and collector stats, and the Perfetto converter's output
against the trace-event schema.

Tier-1 scope: pure-host tests plus a module-scoped region of the SAME
spec shape as test_gateway_binary's ("gwb": 2 shards x 8 eps, 2 devices,
payload width 4) so the in-process jit cache is already warm; every
device op stays <= 64 rows (pow2-floor-64 scatter padding = no new XLA
compiles)."""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import pytest

from akka_tpu.config import Config
from akka_tpu.event.tracing import (NOOP_SPAN, SpanCtx, Tracer,
                                    current_ctx, from_config, reset_ctx,
                                    set_ctx)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import trace_export  # noqa: E402


@pytest.fixture(scope="module")
def region():
    from akka_tpu.gateway import counter_behavior
    from akka_tpu.sharding.device import DeviceEntity, DeviceShardRegion
    spec = DeviceEntity("gwb", counter_behavior(4), n_shards=2,
                        entities_per_shard=8, n_devices=2, payload_width=4)
    return DeviceShardRegion(spec)


def _server(region, tracer, rate=1e9, burst=1e9, replica_cache=None):
    from akka_tpu.gateway import (AdmissionController, GatewayServer,
                                  RegionBackend, SloTracker)
    backend = RegionBackend(region, batch=True, max_batch=64)
    srv = GatewayServer(None, backend, AdmissionController(rate=rate,
                                                           burst=burst),
                        SloTracker(), tracer=tracer,
                        replica_cache=replica_cache)
    return srv, backend


# ---------------------------------------------------------------- sampling
def test_sampling_deterministic_per_seed():
    """THE head-sampling contract: the decision is a pure function of the
    deterministically minted trace id, so two tracers with the same seed
    sample the SAME subset of the same request stream."""
    a = Tracer(sample_rate=0.25, seed=42)
    b = Tracer(sample_rate=0.25, seed=42)
    ids_a = [a.start_trace("t", i) for i in range(256)]
    ids_b = [b.start_trace("t", i) for i in range(256)]
    assert ids_a == ids_b
    sampled = [i for i in ids_a if i]
    assert 0 < len(sampled) < 256  # a real subset at rate 0.25
    # a different seed picks a different subset (2^-256-ish to collide)
    c = Tracer(sample_rate=0.25, seed=43)
    assert [c.start_trace("t", i) for i in range(256)] != ids_a
    # the decision replays from the id alone
    assert all(a.sampled(i) for i in sampled)


def test_sampling_rate_extremes_and_forcing():
    assert all(Tracer(sample_rate=0.0).start_trace() == 0
               for _ in range(32))
    assert all(Tracer(sample_rate=1.0).start_trace() != 0
               for _ in range(32))
    t = Tracer(sample_rate=0.0, force_tenants=["vip"],
               force_request_ids=[77])
    assert t.start_trace("other", 1) == 0
    assert t.start_trace("vip", 1) != 0        # forced tenant
    assert t.start_trace("other", 77) != 0     # forced request id
    # trace id 0 is reserved for "unsampled": minted ids are never 0
    assert all(Tracer(sample_rate=1.0, seed=s).start_trace() != 0
               for s in range(8))


# ------------------------------------------------------------------- spans
def test_unsampled_trace_is_noop_span():
    tr = Tracer(sample_rate=1.0)
    sp = tr.span("x", 0)
    assert sp is NOOP_SPAN
    assert sp.child("y") is sp and sp.ctx is None
    with sp as inner:
        inner.set(ignored=1)
        assert current_ctx() is None  # the quiet path never touches ctx
    assert tr.spans() == []


def test_span_tree_ambient_ctx_and_clocks():
    tr = Tracer(sample_rate=1.0, seed=9)
    steps = iter(range(10, 20))
    tr.step_fn = lambda: next(steps)
    tid = tr.start_trace()
    assert current_ctx() is None
    with tr.span("root", tid, k="v") as root:
        assert current_ctx().span_id == root.span_id
        with root.child("kid") as kid:
            assert kid.trace_id == tid and kid.parent_id == root.span_id
            # an int-trace span inside the block auto-parents to ambient
            auto = tr.span("auto", tid)
            assert auto.parent_id == kid.span_id
        assert current_ctx().span_id == root.span_id  # ctx restored
    assert current_ctx() is None
    rows = tr.of_trace(tid)
    by_name = {r["name"]: r for r in rows}
    assert by_name["root"]["parent"] == 0 and by_name["root"]["k"] == "v"
    assert by_name["kid"]["parent"] == by_name["root"]["span"]
    for r in rows:
        assert r["t1"] >= r["t0"] > 0 and r["ts"] > 0
        assert r["step1"] >= r["step0"] >= 10  # the ATT_STEP axis rode in


def test_retro_emit_and_error_attr():
    tr = Tracer(sample_rate=1.0)
    tid = tr.start_trace()
    t0 = time.monotonic() - 0.5
    tr.emit("late", tid, t0=t0, t1=t0 + 0.25, step0=3, step1=7, slot=1)
    row = tr.of_name("late")[0]
    assert row["t1"] - row["t0"] == pytest.approx(0.25)
    assert (row["step0"], row["step1"], row["slot"]) == (3, 7, 1)
    assert row["ts"] == pytest.approx(time.time() - 0.5, abs=0.25)
    with pytest.raises(RuntimeError):
        with tr.span("boom", tid):
            raise RuntimeError("x")
    assert tr.of_name("boom")[0]["error"] == "RuntimeError"


def test_set_reset_ctx_round_trip():
    ctx = SpanCtx(5, 6)
    tok = set_ctx(ctx)
    assert current_ctx() is ctx
    reset_ctx(tok)
    assert current_ctx() is None


def test_from_config_gating_and_jsonl_sink(tmp_path):
    assert from_config(None) is None
    assert from_config(Config({})) is None  # default off: quiet path
    path = str(tmp_path / "spans.jsonl")
    tr = from_config(Config({"akka": {"tracing": {
        "enabled": True, "sample-rate": 0.5, "seed": 12,
        "jsonl-path": path, "force-tenants": ["vip"]}}}))
    assert tr is not None and tr.sample_rate == 0.5
    assert tr.start_trace("vip") != 0  # forced through rate 0.5
    tid = 0
    while not tid:
        tid = tr.start_trace()
    with tr.span("persisted", tid):
        pass
    tr.close()
    rows = trace_export.load_jsonl(path)
    assert [r["name"] for r in rows] == ["persisted"]
    assert rows[0]["trace"] == tid and rows[0]["kind"] == "span"


# --------------------------------------------------- serving-path integration
def test_thread_hop_parent_child_integrity(region):
    """JSON requests from concurrent client threads ride the AskBatcher's
    dispatcher thread; every ask.member span must still be parented under
    ITS submitter's gw.request root (the ctx that rides next to the ask —
    solo JSON serves through the same columnar window path as binary),
    and no span may reference a parent that was never emitted."""
    tr = Tracer(sample_rate=1.0, seed=21)
    srv, backend = _server(region, tr)
    try:
        def worker(w):
            for i in range(3):
                rep = json.loads(srv.handle_frame(json.dumps(
                    {"id": w * 8 + i, "tenant": f"t{w % 2}",
                     "entity": f"hop-{w}", "op": "add",
                     "value": 1.0}).encode()))
                assert rep["status"] == "ok" and rep["trace"], rep
        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        backend.close()
    spans = tr.spans()
    by_id = {(s["trace"], s["span"]): s for s in spans}
    for s in spans:
        if s["parent"]:
            assert (s["trace"], s["parent"]) in by_id, f"orphan: {s}"
    members = [s for s in spans if s["name"] == "ask.member"]
    assert len(members) == 12  # one per request, across the thread hop
    for m in members:
        assert by_id[(m["trace"], m["parent"])]["name"] == "gw.request"
        assert m["outcome"] == "reply" and m["step1"] >= m["step0"]
    # each trace is one complete request tree rooted at gw.request
    roots = [s for s in spans if s["name"] == "gw.request"]
    assert len(roots) == 12 and all(r["parent"] == 0 for r in roots)


def test_caller_thread_wave_and_wave_id_stats_agreement(region):
    """One binary window = one caller-thread ask wave carrying MANY
    traces: the wave span joins them via member_traces, members parent to
    their own gw.request roots, a same-entity duplicate rides a deferred
    flush, and the span wave_id matches the batcher collector's
    last_wave_id (the spans<->stats cross-check key)."""
    from akka_tpu.serialization import frames
    tr = Tracer(sample_rate=1.0, seed=33)
    srv, backend = _server(region, tr)
    try:
        body = frames.encode_request_batch(
            [1, 2, 3, 4], ["t0"] * 4, ["wv-a", "wv-b", "wv-a", "wv-c"],
            [frames.OP_ADD] * 4, [1.0, 2.0, 3.0, 4.0])
        reps = frames.decode_replies(srv.handle_frame(body))
        assert [r["status"] for r in reps] == ["ok"] * 4
        assert all(r["trace"] for r in reps)
        stats = backend.batcher.stats()
        spans = tr.spans()  # the window's spans, before the extra probe
        # traced binary replies ride version-2 records (trace column)
        rec = frames.decode_reply_batch(srv.handle_binary(
            frames.encode_request_batch([9], ["t0"], ["wv-a"],
                                        [frames.OP_GET], [0.0])))
        assert "trace" in rec.dtype.names
    finally:
        backend.close()
    waves = [s for s in spans if s["name"] == "ask.wave"]
    assert len(waves) == 1
    wave = waves[0]
    assert wave["n_members"] == 4 and wave["n_sampled"] == 4
    assert sorted(wave["member_traces"]) == sorted(r["trace"] for r in reps)
    assert stats["last_wave_id"] == wave["wave_id"]
    members = {}
    by_id = {(s["trace"], s["span"]): s for s in spans}
    for m in (s for s in spans if s["name"] == "ask.member"):
        assert m["wave_id"] == wave["wave_id"]
        assert by_id[(m["trace"], m["parent"])]["name"] == "gw.request"
        members[m["trace"]] = m
    assert len(members) == 4
    # the second wv-a add deferred behind the first (one in-flight ask
    # per destination row) and its span says so
    dup_trace = reps[2]["trace"]
    assert members[dup_trace]["deferred"] is True
    assert sum(1 for m in members.values() if m["deferred"]) == 1
    # wave children carry the same wave_id (flush/step_round/readback)
    kids = [s for s in spans if s["name"].startswith("wave.")]
    assert {s["wave_id"] for s in kids} == {wave["wave_id"]}
    assert any(s["name"] == "wave.flush" and s.get("deferred")
               for s in kids)


def test_wave_ids_monotone_across_waves(region):
    tr = Tracer(sample_rate=1.0, seed=5)
    srv, backend = _server(region, tr)
    try:
        for i in range(3):
            srv.handle_frame(json.dumps(
                {"id": i, "tenant": "t0", "entity": "mono-a", "op": "add",
                 "value": 1.0}).encode())
        stats = backend.batcher.stats()
    finally:
        backend.close()
    ids = sorted(s["wave_id"] for s in tr.of_name("ask.wave"))
    assert len(ids) == 3 and ids == sorted(set(ids))
    assert stats["last_wave_id"] == ids[-1]


def test_replica_read_span_parents_under_request_root(region):
    """A replica-served get emits gw.replica_read parented under ITS
    gw.request root, carrying the step-lag attribute; a fall-through get
    keeps the ask.member parenting — and the whole forest stays
    orphan-free (ISSUE 14 satellite)."""
    from akka_tpu.gateway.replica import ReadReplicaCache
    tr = Tracer(sample_rate=1.0, seed=55)
    cache = ReadReplicaCache(lambda: 0, hot_hits=1, hot_window_s=30.0,
                             hot_ttl_s=30.0)
    srv, backend = _server(region, tr, replica_cache=cache)
    try:
        def req(rid, entity, op, value=0.0):
            return json.loads(srv.handle_frame(json.dumps(
                {"id": rid, "tenant": "t0", "entity": entity, "op": op,
                 "value": value}).encode()))

        assert req(1, "rr-a", "add", 2.0)["status"] == "ok"
        rep = req(2, "rr-a", "get")  # hot + published: replica-served
        assert rep["replica"] is True and rep["step_lag"] == 0
        cold = req(3, "rr-cold", "get")  # hot but never published:
        assert "replica" not in cold     # falls through to the wave
    finally:
        backend.close()
    spans = tr.spans()
    by_id = {(s["trace"], s["span"]): s for s in spans}
    for s in spans:
        if s["parent"]:
            assert (s["trace"], s["parent"]) in by_id, f"orphan: {s}"
    reads = [s for s in spans if s["name"] == "gw.replica_read"]
    assert len(reads) == 1
    assert reads[0]["trace"] == rep["trace"]
    assert reads[0]["step_lag"] == 0
    assert by_id[(reads[0]["trace"], reads[0]["parent"])]["name"] == \
        "gw.request"
    # the replica-served trace never reached the ask wave...
    assert not [s for s in spans if s["name"] == "ask.member"
                and s["trace"] == rep["trace"]]
    # ...while the fall-through get rode it, parented as always
    member = [s for s in spans if s["name"] == "ask.member"
              and s["trace"] == cold["trace"]]
    assert len(member) == 1
    assert by_id[(member[0]["trace"], member[0]["parent"])]["name"] == \
        "gw.request"


# ------------------------------------------------------------------ exporter
def test_exporter_perfetto_schema_and_pause_duration(region, tmp_path):
    """The converter's output must satisfy the trace-event schema the
    validator pins (field/type constraints + per-track nesting), with a
    scale_to-style mesh_expanded FR event rendered as a DURATION block
    ending at its timestamp and a legacy wall-only row aligned via the
    median wall-minus-monotonic offset."""
    tr = Tracer(sample_rate=1.0, seed=17)
    srv, backend = _server(region, tr)
    try:
        for i in range(4):
            rep = json.loads(srv.handle_frame(json.dumps(
                {"id": i, "tenant": "t0", "entity": f"px-{i % 2}",
                 "op": "add", "value": 1.0}).encode()))
            assert rep["status"] == "ok"
    finally:
        backend.close()
    spans = tr.spans()
    now_w, now_m = time.time(), time.monotonic()
    events = [
        {"event": "mesh_expanded", "ts": now_w, "ts_mono": now_m,
         "pause_s": 0.02, "from_shards": 2, "to_shards": 4},
        {"event": "device_checkpoint", "ts": now_w + 0.1,
         "ts_mono": now_m + 0.1, "elapsed_s": 0.005, "step": 64},
        {"event": "device_evicted", "ts": now_w - 1.0, "shard": 1},  # legacy
    ]
    doc = trace_export.to_perfetto(spans, events)
    assert trace_export.validate_trace(doc) == []
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] != "M"}
    pause = evs["mesh_expanded"]
    assert pause["ph"] == "X"
    assert pause["dur"] == pytest.approx(0.02 * 1e6)
    assert evs["device_checkpoint"]["dur"] == pytest.approx(0.005 * 1e6)
    assert evs["device_evicted"]["ph"] == "i"  # wall-only row: instant
    assert all(e["ts"] >= 0 for e in doc["traceEvents"] if "ts" in e)
    # wave spans share the dedicated waves track; requests get own tids
    wave_tids = {e["tid"] for e in doc["traceEvents"]
                 if e.get("name", "").startswith(("ask.wave", "wave."))}
    assert wave_tids == {trace_export.TID_WAVES}
    # the CLI round-trips the same document through --validate
    sp_path, fr_path = tmp_path / "s.jsonl", tmp_path / "f.jsonl"
    sp_path.write_text("".join(json.dumps(s) + "\n" for s in spans))
    fr_path.write_text("".join(json.dumps(e) + "\n" for e in events))
    out = tmp_path / "trace.json"
    rc = trace_export.main(["--spans", str(sp_path), "--flight",
                            str(fr_path), "--out", str(out), "--validate"])
    assert rc == 0
    assert json.load(open(out))["traceEvents"]


def test_validator_rejects_broken_documents():
    bad_overlap = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0,
         "dur": 10.0},
        {"name": "b", "ph": "X", "pid": 1, "tid": 1, "ts": 5.0,
         "dur": 10.0},
    ]}
    assert any("nesting" in e for e in
               trace_export.validate_trace(bad_overlap))
    assert trace_export.validate_trace({"traceEvents": [
        {"name": "x", "ph": "Q", "pid": 1, "tid": 1}]})
    assert trace_export.validate_trace({"traceEvents": [
        {"name": "m", "ph": "M", "pid": 1, "tid": 0, "args": {}}]})
    assert trace_export.validate_trace({}) == ["traceEvents is not a list"]
