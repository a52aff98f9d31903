"""Tier-1 smoke for the delivery-kernel bench surface (bench.py --config
modes): at tiny scale, every mode of the table must deliver correctly, and
slots-mode ordered delivery must stay within a fixed regression budget of
the scatter reduction — the 350x slots/merge gap this rewrite closed must
not silently reopen."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bench
from akka_tpu.ops import segment as sg


# slots does strictly more work than scatter (per-message placement, FIFO,
# spill bookkeeping). Pre-rewrite the ratio was ~500x at full scale; the
# ranked kernels hold it to low single digits. The budget is generous so a
# noisy CI box cannot flake, while a wide-sort regression (two orders of
# magnitude) still fails loudly.
SLOTS_VS_SCATTER_BUDGET = 12.0


def test_modes_smoke_and_slots_budget():
    out = bench.bench_modes(n=2048, steps=6)

    for mode in ("merge", "sort", "scatter", "merge_reference", "slots",
                 "slots_reference"):
        assert out[mode]["ok"], (mode, out[mode])
        assert out[mode]["msgs_per_sec"] > 0

    ratio = out["slots"]["ms_per_step"] / out["scatter"]["ms_per_step"]
    assert ratio <= SLOTS_VS_SCATTER_BUDGET, (
        f"slots {out['slots']['ms_per_step']}ms/step vs scatter "
        f"{out['scatter']['ms_per_step']}ms/step: ratio {ratio:.1f} blew "
        f"the {SLOTS_VS_SCATTER_BUDGET}x budget — ordered delivery has "
        f"regressed toward the wide-sort kernels")


def test_supervision_overhead_budget():
    """ISSUE 2 satellite: in-graph supervision with ZERO injected faults
    must cost <= 5% of step time. The whole supervision pass is
    cond-gated on "any lane failed OR mail for a dead supervised lane",
    so a quiet step pays only that predicate (a couple of reductions) —
    measured ~0-3% at 8k both on a whole CPU and under this suite's
    8-virtual-device conftest, where the ungated pass's ~25 small ops
    once cost 30%+ from per-op dispatch on a split thread pool.
    bench_supervision builds all variants first and interleaves best-of
    timing windows so drift cannot land in one delta; the budget keeps
    headroom over the 5% contract for CI-box noise — a pass regressing
    to per-lane host work would blow past any constant regardless."""
    out = bench.bench_supervision(n=8192, steps=6)
    if out["overhead_pct"] > 15.0:
        # one conditional retry absorbs a cross-suite load spike on a
        # shared box; a real ungated-pass regression fails every round
        out2 = bench.bench_supervision(n=8192, steps=6)
        if out2["overhead_pct"] < out["overhead_pct"]:
            out = out2
    assert out["quiet_ok"], out  # zero faults -> zero directive traffic
    assert out["chaos_ok"], out  # injected crashes -> in-graph restarts
    assert out["overhead_pct"] <= 15.0, (
        f"supervision overhead {out['overhead_pct']}% at smoke scale "
        f"(contract: <=5% at bench scale): {out}")


def test_metrics_overhead_budget():
    """ISSUE 7 satellite: the in-graph metric slab with NO traffic must
    cost <= 1% of step time at bench scale (64k lanes). Every histogram
    update is behind one busy predicate (any inbox row valid, any retry
    counter grew, any ask latch newly latched), so a quiet step pays only
    that predicate and a cond skip — and the slab must stay EMPTY (epoch
    0), not merely cheap: idle-step bucket-0 samples would both skew the
    occupancy histogram and defeat the gate. bench_metrics_overhead
    builds all four variants first and interleaves best-of windows
    (the bench_supervision drift discipline); the smoke budget keeps
    headroom over the 1% contract for CI-box noise and the suite's
    8-virtual-device conftest split — an ungated slab samples 4 lanes x
    16 buckets every step and lands at 30%+ regardless of the constant."""
    out = bench.bench_metrics_overhead(n=8192, steps=6)
    assert out["quiet_ok"], out   # quiet run left the slab empty
    assert out["active_ok"], out  # seeded run sampled the traffic lanes
    assert out["quiet_overhead_pct"] <= 15.0, (
        f"metric-slab quiet overhead {out['quiet_overhead_pct']}% at smoke "
        f"scale (contract: <=1% at 64k-lane bench scale): {out}")


def test_checkpoint_overhead_budget():
    """ISSUE 4 satellite: the auto-checkpoint cadence at interval 256 must
    cost <= 5% of quiet-path step time at bench scale. bench_checkpoint
    warms the snapshot path first (orbax bring-up on the FIRST save is
    one-time tens of ms the cadence never pays again) and interleaves
    best-of windows like bench_supervision. Measured ~2-5% at 32k on a
    whole CPU; the smoke budget keeps headroom over the 5% contract for
    CI-box noise and the suite's 8-virtual-device conftest split — a
    regression to per-step snapshots or an unwarmed save path lands at
    100%+ regardless of the constant."""
    out = bench.bench_checkpoint(n=32768, interval=256, windows=2)
    if out["overhead_pct"] > 10.0:
        # one conditional retry absorbs a cross-suite load spike on a
        # shared box; per-step snapshots fail every round at 100%+
        out2 = bench.bench_checkpoint(n=32768, interval=256, windows=2)
        if out2["overhead_pct"] < out["overhead_pct"]:
            out = out2
    assert out["ok"], out
    assert out["snapshot_bytes"] > 0
    assert out["overhead_pct"] <= 10.0, (
        f"checkpoint overhead {out['overhead_pct']}% at smoke scale "
        f"(contract: <=5% at bench scale, interval 256): {out}")


@pytest.mark.slow  # ~9 s: demoted to the slow tier (ISSUE 18 budget
# note) — the rank-family perf claim stays tier-1-guarded by
# test_counting_slots_vs_wide_budget; this is the wider modes sweep
def test_modes_smoke_ranked_beats_reference():
    """The reason the backend seam exists: at any scale, ranked merge and
    slots must not be SLOWER than the frozen wide-sort kernels they
    replace (equal is fine at trivial sizes)."""
    out = bench.bench_modes(n=4096, steps=4)
    assert (out["merge"]["ms_per_step"]
            <= 1.5 * out["merge_reference"]["ms_per_step"])
    assert (out["slots"]["ms_per_step"]
            <= 1.5 * out["slots_reference"]["ms_per_step"])
    recv_ok = [out[k]["ok"] for k in out if "msgs_per_sec" in out[k]]
    assert all(recv_ok)


def test_counting_slots_vs_wide_budget(monkeypatch):
    """ISSUE 6 tentpole budget: the counting-sort slots path must stay
    >= 5x faster than the r05 wide-sort kernel's ms/step at the 64k bench
    shape (measured ~7x live, ~12x on a quiet box: 28ms vs 196ms). Both
    legs are timed best-of interleaved under the same load so machine
    noise cancels in the ratio; a rank phase regressing toward a payload
    sort collapses it to ~1x regardless of the constant."""
    monkeypatch.setattr(sg, "_auto_rank_strategy",
                        lambda m, n, platform: "counting")
    m, n = (1 << 16) + 8, 1 << 16
    rng = np.random.default_rng(7)
    dst = jnp.asarray(rng.integers(0, n, size=m).astype(np.int32))
    mtype = jnp.ones((m,), jnp.int32)
    payload = jnp.asarray(rng.standard_normal((m, 4)).astype(np.float32))
    ok = jnp.ones((m,), bool)

    def make(backend):
        return jax.jit(lambda d, t, p, v: sg.deliver_slots(
            d, t, p, v, n, 2, backend=backend))

    fc, fw = make("xla"), make("reference")
    jax.block_until_ready(fc(dst, mtype, payload, ok))   # compile
    jax.block_until_ready(fw(dst, mtype, payload, ok))
    bc = bw = float("inf")
    for attempt in range(2):
        for _ in range(4):
            t0 = time.perf_counter()
            jax.block_until_ready(fc(dst, mtype, payload, ok))
            bc = min(bc, time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(fw(dst, mtype, payload, ok))
            bw = min(bw, time.perf_counter() - t0)
        if bw >= 5.0 * bc:
            break
        # conditional second best-of window: a cross-suite load spike
        # inflates the fast leg's min; a rank-phase regression stays ~1x
    assert bw >= 5.0 * bc, (
        f"counting slots {bc * 1e3:.1f}ms/step vs wide reference "
        f"{bw * 1e3:.1f}ms/step at 64k: ratio {bw / bc:.1f} fell under "
        f"the 5x budget — the counting rank phase has regressed")


def test_pallas_interpret_modes_agree():
    """ISSUE 6 stage B smoke: deliver(mode="pallas") and the ring slots
    backend must agree with the ranked kernels in interpret mode —
    integer fields bit-identical, float sums allclose (the ring
    accumulates in arrival order, a different association)."""
    from akka_tpu.ops import pallas_mailbox as pm
    m, n, p, slots = 300, 13, 3, 2
    rng = np.random.default_rng(20260805)
    dst = jnp.asarray(rng.integers(-1, n + 1, size=m).astype(np.int32))
    mtype = jnp.asarray(rng.integers(1, 5, size=m).astype(np.int32))
    payload = jnp.asarray(rng.standard_normal((m, p)).astype(np.float32))
    ok = jnp.asarray(rng.random(m) > 0.1)
    assert pm.supported(n, p, slots=slots)

    ranked = sg.deliver(dst, payload, ok, n, need_max=True, mode="merge",
                        backend="xla")
    ring = sg.deliver(dst, payload, ok, n, need_max=True, mode="pallas")
    np.testing.assert_array_equal(np.asarray(ring.count),
                                  np.asarray(ranked.count))
    np.testing.assert_array_equal(np.asarray(ring.max),
                                  np.asarray(ranked.max))
    np.testing.assert_allclose(np.asarray(ring.sum), np.asarray(ranked.sum),
                               rtol=1e-4, atol=1e-3)

    rslots = sg.deliver_slots(dst, mtype, payload, ok, n, slots,
                              need_max=True, backend="xla")
    pslots = sg.deliver_slots(dst, mtype, payload, ok, n, slots,
                              need_max=True, backend="pallas")
    for f in ("types", "valid", "count", "dropped", "max"):
        np.testing.assert_array_equal(
            np.asarray(getattr(pslots, f)), np.asarray(getattr(rslots, f)),
            err_msg=f"pallas slots field {f}")
    # ring payloads: only valid slots are contractual (invalid slots are
    # zeros in both kernels, but assert through the mask anyway)
    vmask = np.asarray(rslots.valid)[..., None]
    np.testing.assert_array_equal(np.asarray(pslots.payload) * vmask,
                                  np.asarray(rslots.payload) * vmask)
    np.testing.assert_allclose(np.asarray(pslots.sum),
                               np.asarray(rslots.sum), rtol=1e-4, atol=1e-3)

    # unsupported options (spill generations) fall back to ranked:
    # bit-identical everywhere including float fields
    ref = sg.deliver_slots(dst, mtype, payload, ok, n, slots, spill_cap=8,
                           backend="xla")
    fb = sg.deliver_slots(dst, mtype, payload, ok, n, slots, spill_cap=8,
                          backend="pallas")
    for f in ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(fb, f)), np.asarray(getattr(ref, f)),
            err_msg=f"pallas fallback field {f}")


def test_pallas_request_on_tpu_raises(monkeypatch):
    """ISSUE 22: on a TPU the compiler refuses the ring kernel, so
    supported() is false there and an explicit request raises with the
    compiler's message instead of quietly running the ranked kernels. No
    TPU needed: the platform is what segment.py resolved, passed down."""
    from akka_tpu.ops import pallas_mailbox as pm
    assert not pm.supported(13, 3, slots=2, platform="tpu")
    assert pm.supported(13, 3, slots=2, platform="cpu")
    monkeypatch.setattr(sg, "_resolve_platform", lambda x: "tpu")
    dst = jnp.zeros((8,), jnp.int32)
    payload = jnp.ones((8, 3), jnp.float32)
    ok = jnp.ones((8,), bool)
    with pytest.raises(NotImplementedError, match="Mosaic"):
        sg.deliver(dst, payload, ok, 13, mode="pallas")
    with pytest.raises(NotImplementedError, match="Mosaic"):
        sg.deliver_slots(dst, dst, payload, ok, 13, 2, backend="pallas")


def test_merge_sums_are_prefix_diffs_so_ask_reply_ids_need_scatter():
    """ISSUE 22 (found on the chip): merge/sort take a segment's sum as the
    difference of ONE running prefix over all messages, so integer-valued
    f32 payloads stay exact only while that prefix stays under 2^24. Ask
    reply-to row ids near 2^20 ride a payload column: past 16 asks in one
    step their sums come back off by one or two and replies are misrouted.
    Scatter-add accumulates each segment alone. Hence the layers that own
    the ask protocol build their runtimes with bridge.ASK_DELIVERY (asserted
    where each is built: test_bridge, test_ask_batch, test_failover), while
    a runtime that is merely wired for the latch bit stays on "auto"."""
    from akka_tpu.batched import Emit, behavior
    from akka_tpu.batched.bridge import ASK_DELIVERY
    from akka_tpu.batched.step import StepCore
    k, n = 64, 64
    ids = (1 << 20) + np.arange(k, dtype=np.float32)  # promise rows
    dst = jnp.arange(k, dtype=jnp.int32)              # one ask per entity
    payload = jnp.zeros((k, 4), jnp.float32).at[:, 3].set(ids)
    ok = jnp.ones((k,), bool)
    exact = sg.deliver(dst, payload, ok, n, mode=ASK_DELIVERY)
    np.testing.assert_array_equal(np.asarray(exact.sum)[:, 3], ids)
    for backend in ("xla", "reference"):
        lossy = sg.deliver(dst, payload, ok, n, mode="merge", backend=backend)
        assert (np.asarray(lossy.sum)[:, 3] != ids).any(), backend

    @behavior("noop", {})
    def noop(state, inbox, ctx):
        return {}, Emit.none(1, 4)

    core = StepCore([noop], n_local=8, payload_width=4, out_degree=1,
                    payload_dtype=jnp.float32,
                    attention_latch_col="__promise_replied")
    assert core.delivery == "auto"  # telemetry wiring decides no kernel


def test_failover_mttr_budget():
    """ISSUE 5 satellite: automatic failover (detection bookkeeping +
    quarantine + rebuild + snapshot restore + WAL replay + first drain)
    must stay within a fixed multiple of ONE manual checkpoint restore on
    the same surviving mesh — the sentinel may not add open-ended work on
    top of the recovery substrate it drives. Both legs pay a fresh XLA
    compile for the new shard count, so the ratio prices the sentinel's
    machinery, not the compiler; measured ~2x at smoke scale, and the 8x
    budget leaves room for CI noise while a sentinel that re-steps the
    whole horizon (or recompiles per drain) blows past any constant."""
    out = bench.bench_failover(n=1536, steps=24)
    assert "skipped" not in out, out  # conftest pins 8 virtual devices
    assert out["ok"], out
    assert out["events"]["device_evicted"] == 1, out
    assert out["events"]["failover_completed"] == 1, out
    assert out["mttr_s"] > 0
    assert out["mttr_s"] <= 8.0 * out["restore_s"] + 2.0, (
        f"failover MTTR {out['mttr_s']}s vs manual restore "
        f"{out['restore_s']}s: blew the 8x-plus-slack budget — detection "
        f"or rebuild is doing non-constant extra work: {out}")


def test_bridge_pipeline_throughput_budget():
    """ISSUE 3 satellite: the depth-k attention-word pump must never be
    SLOWER than the synchronous pump round it replaced (step +
    block_until_ready + unconditional wide promise readback). The bench
    times both against the same handle with an unresolved waiter
    outstanding, so the sync leg pays the wide readback every round
    exactly like the pre-pipeline pump servicing an in-flight ask; the
    pipelined leg drains one [ATT_WORDS] word instead. >= rather than a
    ratio: the margin is ~2x on CPU but the contract is only "the
    pipeline is free", and best-of-3 windows keep scheduler noise out."""
    out = bench.bench_bridge_latency(20, depth=4)
    assert out["pipelined"]["steps_per_sec"] >= out["sync"]["steps_per_sec"], out
    # pipeline depth is recorded in the artifact (watchdog parses it)
    assert out["depth"] == 4
    assert out["pipelined"]["pipeline"]["depth"] == 4
    assert out["pipelined"]["pipeline"]["steps"] > 0
