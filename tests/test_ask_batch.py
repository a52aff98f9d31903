"""Batched ask engine (akka_tpu/sharding/ask_batch.py): solo bit-parity
with the pre-batching ask path, per-entity linearization via wave
scheduling, conserved-value correctness under concurrent gateway traffic
on BOTH delivery backends, per-ask timeout retirement mid-batch, typed
pool exhaustion mid-batch, and AskBatcher window coalescing.

Tier-1 budget: every region here is tiny (2 shards x 8 entities, one
virtual device) and registered in _REGIONS so the budget-guard test can
assert nobody quietly grows a compile-heavy system into this module.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from akka_tpu.batched.bridge import AskPoolExhausted
from akka_tpu.gateway import (AdmissionController, GatewayServer,
                              RegionBackend, SloTracker, counter_behavior)
from akka_tpu.sharding import AskBatcher
from akka_tpu.sharding.device import DeviceEntity, DeviceShardRegion

# both delivery kernel families: the conserved-value invariant must be
# bit-identical across them (integer-valued float adds are exact, so any
# divergence is a routing/misdelivery bug, not rounding)
_BACKENDS = (None, "reference")
_REGIONS = {}


def _region(backend):
    if backend not in _REGIONS:
        spec = DeviceEntity(f"ab-{backend or 'auto'}", counter_behavior(4),
                            n_shards=2, entities_per_shard=16, n_devices=1,
                            payload_width=4, delivery_backend=backend)
        _REGIONS[backend] = DeviceShardRegion(spec)
    return _REGIONS[backend]


def _total(region, entity_id: str) -> float:
    ref = region.entity_ref(entity_id)
    return float(np.asarray(
        region.system.read_state("total", np.asarray([ref.row], np.int32)))[0])


# ----------------------------------------------------------------- parity
def test_solo_and_batched_asks_bit_identical():
    """A batch of one runs the exact old step schedule; a batch of N to
    distinct entities returns the same replies the serialized loop
    returns. Full-payload comparison, not just the total column."""
    region = _region(None)
    values = [1.0, 2.0, 3.0, 4.0]
    serial = []
    for i, v in enumerate(values):
        ref = region.entity_ref(f"par-s{i}")
        serial.append(np.asarray(region.ask(ref.shard, ref.index, [v])))
    refs = [region.entity_ref(f"par-b{i}") for i in range(len(values))]
    batched = region.ask_many(
        [(r.shard, r.index, [v]) for r, v in zip(refs, values)])
    for s, b in zip(serial, batched):
        assert not isinstance(b, BaseException), b
        np.testing.assert_array_equal(s, np.asarray(b))
    # ask() itself is a batch of one: repeating an add doubles the total
    ref = region.entity_ref("par-s0")
    again = np.asarray(region.ask(ref.shard, ref.index, [values[0]]))
    assert float(again[0]) == 2 * values[0]


def test_same_entity_batch_linearized():
    """Dense-inbox reduce SUMS concurrent payloads to one row, so the
    engine must serialize same-row asks across waves: each reply is a
    distinct prefix sum, not a summed mess."""
    region = _region(None)
    ref = region.entity_ref("lin-0")
    out = region.ask_many([(ref.shard, ref.index, [v])
                           for v in (1.0, 2.0, 4.0)])
    assert [float(np.asarray(r)[0]) for r in out] == [1.0, 3.0, 7.0]
    assert _total(region, "lin-0") == 7.0


# ------------------------------------------------- concurrency + backends
def _drive_gateway(region, entities, per_worker=4, workers=6):
    """Mixed add/get from `workers` threads through handle_frame on a
    batched backend; returns (sent_sum, acked adds per entity, replies)."""
    import json

    from akka_tpu.gateway.ingress import encode_body

    backend = RegionBackend(region, batch_window_s=2e-3, max_batch=8)
    slo = SloTracker()
    srv = GatewayServer(None, backend,
                        AdmissionController(rate=1e9, burst=1e9), slo)
    sent = {e: [] for e in entities}
    acks = {e: [] for e in entities}
    errs = []

    def worker(w):
        for i in range(per_worker):
            ent = entities[(w + i) % len(entities)]
            val = float(w * per_worker + i + 1)
            body = encode_body({"id": w * 100 + i, "tenant": f"t{w % 2}",
                                "entity": ent, "op": "add", "value": val})
            rep = json.loads(srv.handle_frame(body))
            if rep.get("status") != "ok":
                errs.append(rep)
                continue
            sent[ent].append(val)
            acks[ent].append(float(rep["value"]))

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    backend.close()
    assert not errs, errs
    return sent, acks


@pytest.mark.parametrize("backend", _BACKENDS)
def test_concurrent_gateway_asks_conserved_and_linearized(backend):
    """N threads of mixed traffic: every acked add's reply is a running
    total on a per-entity linearized chain (sorted replies differ by
    exactly the multiset of that entity's values), and the final device
    totals equal the sent sums — integer floats, so exact."""
    region = _region(backend)
    entities = ["cc-a", "cc-b", "cc-c"]
    sent, acks = _drive_gateway(region, entities)
    # conserved-value invariant (acceptance): nothing lost, nothing conjured
    acked_sum = sum(a[-1] if a else 0.0 for a in
                    (sorted(acks[e]) for e in entities))
    final_total = sum(_total(region, e) for e in entities)
    sent_sum = sum(sum(sent[e]) for e in entities)
    assert acked_sum <= final_total <= sent_sum
    for ent in entities:
        assert len(acks[ent]) == len(sent[ent])
        chain = sorted(acks[ent])
        # strictly increasing prefix sums of SOME order of sent values
        diffs = [chain[0]] + [b - a for a, b in zip(chain, chain[1:])]
        assert sorted(diffs) == sorted(sent[ent])
        assert chain[-1] == sum(sent[ent]) == _total(region, ent)
    # same workload shape on the other backend lands bit-identical totals
    # (checked once both parametrizations have run)
    _FINALS[backend] = {e: _total(_region(backend), e) for e in entities}
    if len(_FINALS) == len(_BACKENDS):
        a, b = (_FINALS[k] for k in _BACKENDS)
        assert a == b


_FINALS = {}


# ------------------------------------------------ timeout + pool mid-batch
def test_mid_batch_timeout_retires_only_that_slot():
    """One member asks a never-spawned row (no behavior -> no reply): it
    times out and retires ITS slot; batch-mates get correct replies."""
    region = _region(None)
    ref = region.entity_ref("to-live")
    dead_idx = region.eps - 1  # index never handed out by entity_ref here
    with region._lock:
        assert dead_idx >= region._spawned[ref.shard]  # truly dead row
    before = region.ask_pool_stats()
    out = region.ask_many([(ref.shard, ref.index, [5.0]),
                           (ref.shard, dead_idx, [1.0])],
                          steps=2, max_extra_steps=2)
    assert float(np.asarray(out[0])[0]) == 5.0
    assert isinstance(out[1], TimeoutError)
    assert "unanswered after 4 steps" in str(out[1])
    after = region.ask_pool_stats()
    assert after["retired"] == before["retired"] + 1
    # the pool still serves: a follow-up solo ask succeeds
    assert float(np.asarray(
        region.ask(ref.shard, ref.index, [1.0]))[0]) == 6.0


def test_mid_batch_pool_exhaustion_is_per_member():
    """Park the free list down to 2 slots: a batch of 3 gets two replies
    and ONE typed AskPoolExhausted, position-aligned; batch-mates are
    unaffected (acceptance: one member's failure never fails the rest)."""
    region = _region(None)
    region._ensure_promise_rows()
    region._reclaim_promise_slots()
    refs = [region.entity_ref(f"exh-{i}") for i in range(3)]
    with region._lock:
        free = region._promise_free
        parked, region._promise_free = free[2:], free[:2]
    try:
        out = region.ask_many([(r.shard, r.index, [1.0]) for r in refs])
    finally:
        with region._lock:
            region._promise_free.extend(parked)
    assert isinstance(out[2], AskPoolExhausted)
    assert "promise rows exhausted" in str(out[2])
    for r in out[:2]:
        assert float(np.asarray(r)[0]) == 1.0


# ----------------------------------------------------------- AskBatcher
def test_batcher_window_coalesces_concurrent_submits():
    """Submits arriving within the window share one device round: 4
    barrier-released threads coalesce instead of paying 4 serialized
    asks; stats() carries the evidence the bench artifact asserts on."""
    region = _region(None)
    batcher = AskBatcher(region, max_batch=4, window_s=0.25)
    refs = [region.entity_ref(f"coal-{i}") for i in range(4)]
    barrier = threading.Barrier(4)
    replies = [None] * 4

    def go(i):
        barrier.wait()
        replies[i] = batcher.ask(refs[i].shard, refs[i].index, [float(i + 1)])

    threads = [threading.Thread(target=go, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        for i, r in enumerate(replies):
            assert float(np.asarray(r)[0]) == float(i + 1)
        st = batcher.stats()
        assert st["asks"] == 4.0
        assert st["batches"] <= 2.0  # barrier + 250ms window: coalesced
        assert st["max_batch_size"] >= 2.0
        assert st["multi_ask_batches"] >= 1.0
        assert st["pending"] == 0.0
    finally:
        batcher.close()
    with pytest.raises(RuntimeError, match="closed"):
        batcher.submit(0, 0, [1.0])


def test_batcher_caps_batch_at_promise_pool():
    region = _region(None)
    assert AskBatcher(region, max_batch=4096).max_batch == region.eps


# ----------------------------------------------------------- budget guard
def test_tier1_budget_all_regions_stay_tiny():
    """Memory note: the tier-1 suite runs near its 870s timeout. Every
    region this module compiles must stay tiny — <= 64 device rows keeps
    the XLA step-program compiles in the seconds, not the minutes."""
    assert _REGIONS, "region cache unexpectedly empty"
    for backend, region in _REGIONS.items():
        assert region.system.capacity <= 64, (backend,
                                              region.system.capacity)
        assert region.eps <= 16 and region.spec.n_shards <= 2
        # whatever kernel family is pinned, the region delivers asks with
        # the exact-per-segment mode (bridge.ASK_DELIVERY)
        assert region.system._core.delivery == "scatter"
