"""The plain references, and their controls: each with one of the
configuration's guarantees broken has to come out as not correct."""

import numpy as np
import pytest

from benchmark import peaks, rooflines
from benchmark.harness import BENCH, load_json
from benchmark.reference import controls, ring

RING_TRAFFIC = load_json(BENCH, "traffic", "ring-full.json")
LIMITS = load_json(BENCH, "configs", "actors-1m.json")["limits"]


# ------------------------------------------------------------------ ring
@pytest.mark.parametrize("n,stride,t", [(64, 1, 5), (64, 16, 9), (60, 7, 64)])
def test_ring_power_shortcut_equals_the_literal_steps(n, stride, t):
    p0 = ring.seed_payload(n, 4, 3, RING_TRAFFIC)
    dst, pay, recv = np.arange(n), p0.copy(), np.zeros(n, np.int64)
    for _ in range(t):
        dst, pay, recv = ring.step(dst, pay, recv, stride)
    received, payload_at = ring.after(n, stride, p0, t)
    assert (recv == received).all()
    at = np.empty_like(pay)
    at[dst] = pay
    assert (at == payload_at).all()


def test_ring_literal_step_sums_messages_that_meet():
    dst = np.array([2, 2, 0])
    pay = np.array([[1, 2, 0, 0], [1, 3, 0, 0], [1, 5, 0, 0]], np.float32)
    d, p, r = ring.step(dst, pay, np.zeros(4, np.int64), 1)
    assert d.tolist() == [1, 3] and r.tolist() == [1, 0, 2, 0]
    assert p.tolist() == [[1, 5, 0, 0], [2, 5, 0, 0]]


def test_ring_payloads_keep_every_prefix_sum_exact_in_f32():
    p = ring.seed_payload(1 << 20, 4, 9, RING_TRAFFIC)
    assert (p[:, 0] == 1).all() and p.max() <= RING_TRAFFIC["payload_max"]
    assert p.sum(axis=0).max() < 2 ** 24
    assert not (p == ring.seed_payload(1 << 20, 4, 10, RING_TRAFFIC)).all()


def ring_outcome(n=256, stride=1, t=12, seed=5):
    p0 = ring.seed_payload(n, 4, seed, RING_TRAFFIC)
    return p0, controls.ring_reference_outcome(n, stride, p0, t)


def verdict(numbers):
    return all(c["value"] <= c["limit"] for c in numbers.values())


def test_ring_reference_in_the_programs_place_is_correct():
    p0, got = ring_outcome()
    assert verdict(ring.judge(256, 1, p0, 12, got, LIMITS))


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("control", sorted(controls.RING))
def test_ring_control_with_one_guarantee_broken_is_not_correct(control, seed):
    p0, got = ring_outcome(seed=seed)
    controls.RING[control](got)
    assert not verdict(ring.judge(256, 1, p0, 12, got, LIMITS))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_ring_controls_as_the_chip_tool_judges_them(seed):
    """`benchmark/tools/control.py` wants every control not correct and the
    unbroken reference, in the program's place, correct."""
    p0 = ring.seed_payload(256, 4, seed, RING_TRAFFIC)
    out = controls.judge_ring_controls(256, 16, p0, 40, LIMITS)
    assert verdict(out.pop("reference_itself"))
    assert set(out) == set(controls.RING)
    assert not any(verdict(numbers) for numbers in out.values())


# ---------------------------------------------------- bytes and the peaks
def test_ring_step_bytes_against_a_hand_count():
    conf = load_json(BENCH, "configs", "actors-1m.json")
    # 1,048,576 actors: 4 B of state read + written, a 24 B message
    # (dst 4, type 4, payload 16) written + read
    assert rooflines.ring_step_bytes(conf) == 1048576 * (4 * 2 + 24 * 2)
    assert rooflines.ring_step_bytes(conf) == 58_720_256
    x = load_json(BENCH, "configs", "sharded-ring-256x4096.json")
    assert rooflines.ring_step_bytes(x, chips=4) == 58_720_256 / 4


def test_roofline_share_by_hand_and_unknown_devices_are_an_error():
    v5e = peaks.peaks_of("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    assert "v5e" in v5e["source"]
    # 58,720,256 B at 819 GB/s is 71.7 us: of a 20.5 ms step, 0.35%
    assert rooflines.roofline_share(58_720_256, 20.5e-3, v5e) == \
        pytest.approx(0.3497, abs=1e-3)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_of("cpu")
