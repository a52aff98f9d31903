"""Plain reference for the fan-in aggregator: numpy only, nothing of the
program.

Semantics (BASELINE.json config 3; the shape of the Akka guide's IoT example,
device -> device group). Leaf i holds the ref of one collector, two readings
a_i and b_i, an alarm level (0: it never alarms) and a phase. At step t, the
system's own step counter at the start of the step (0 for the first), it
tells its collector one message of four columns

    [1, a_i, (b_i + t) mod L, alarm_i(t)]
    alarm_i(t) = level_i if level_i > 0 and (t + phase_i) mod A == 0 else 0

with L = `reading_levels` and A = `alarm_period` of the traffic file. A
message told at step t is received at step t + 1. A collector that receives
k > 0 messages at a step adds k to `msgs`, each column's sum to `sum0..sum3`,
and with `top` the largest column 3 among them: `alarms += top > 0`,
`peak_total += top`, `last_max = top`. A collector that receives none does
not run. Totals are int32 in the system and wrap; they are compared modulo
2^32, so a run stays exact however many steps it makes.

`step` is the literal rule for one step. `after` is the same rule through
its period, lcm(L, A) steps: the aggregates of each phase once, then each
total as the sum over phases of (steps of that phase) x aggregate, which is
what a run of thousands of steps can afford. tests/benchmark holds the two
against each other."""

from __future__ import annotations

import math

import numpy as np

LEAF_COLUMNS = ("collector", "reading_a", "reading_b", "alarm_level", "phase")
TOTALS = ("msgs", "sum0", "sum1", "sum2", "sum3", "alarms", "peak_total")
COLLECTOR_COLUMNS = TOTALS + ("last_max",)
WIDTH = 4


def seed_leaves(n_leaves: int, n_collectors: int, seed: int,
                traffic: dict) -> dict:
    """The deployment's leaves, from the seed: the collector each is wired to
    (uniform), two readings in [0, L), and for one leaf in `alarm_one_in` an
    alarm level in [1, L) with a phase in [0, A). Small integers, so that
    every per-step column total stays below 2^24 and the program's
    prefix-difference sums are exact f32 (docs/DELIVERY_KERNELS.md)."""
    rng = np.random.default_rng([int(seed), 0x46414E49])
    levels = int(traffic["reading_levels"])
    capable = rng.integers(0, int(traffic["alarm_one_in"]), n_leaves) == 0
    return {
        "collector": rng.integers(0, n_collectors, n_leaves),
        "reading_a": rng.integers(0, levels, n_leaves),
        "reading_b": rng.integers(0, levels, n_leaves),
        "alarm_level": np.where(capable,
                                rng.integers(1, levels, n_leaves), 0),
        "phase": rng.integers(0, int(traffic["alarm_period"]), n_leaves)}


def period(traffic: dict) -> int:
    return math.lcm(int(traffic["reading_levels"]),
                    int(traffic["alarm_period"]))


def emissions(t: int, leaves: dict, traffic: dict) -> np.ndarray:
    """[n_leaves, 4] int64: what every leaf tells at step t."""
    fires = (leaves["alarm_level"] > 0) & (
        (t + leaves["phase"]) % int(traffic["alarm_period"]) == 0)
    return np.stack([
        np.ones_like(leaves["reading_a"]), leaves["reading_a"],
        (leaves["reading_b"] + t) % int(traffic["reading_levels"]),
        np.where(fires, leaves["alarm_level"], 0)], axis=1).astype(np.int64)


def zero_state(n_collectors: int) -> dict:
    return {k: np.zeros(n_collectors, np.int64) for k in COLLECTOR_COLUMNS}


def step(t: int, inbox, state: dict, leaves: dict, traffic: dict):
    """One literal step: the collectors receive `inbox` (dst, payload), the
    leaves tell step t's readings. Returns (next inbox, next state)."""
    dst, payload = inbox
    n = state["msgs"].shape[0]
    count = np.bincount(dst, minlength=n)
    sums = np.zeros((n, WIDTH), np.int64)
    np.add.at(sums, dst, payload)
    top = np.zeros(n, np.int64)
    np.maximum.at(top, dst, payload[:, 3])
    got = count > 0
    new = {k: v.copy() for k, v in state.items()}
    new["msgs"] += count
    for j in range(WIDTH):
        new[f"sum{j}"] += sums[:, j]
    new["alarms"] += (top > 0) & got
    new["peak_total"] += np.where(got, top, 0)
    new["last_max"] = np.where(got, top, state["last_max"])
    return (leaves["collector"], emissions(t, leaves, traffic)), new


def phase_aggregates(leaves: dict, traffic: dict, n_collectors: int) -> dict:
    """Per phase p of the period, what the collectors gain from the messages
    told at a step t = p (mod period): each column of `TOTALS` as
    [period, n_collectors], and `top` the same way."""
    wired = leaves["collector"]
    count = np.bincount(wired, minlength=n_collectors)
    agg = {k: [] for k in TOTALS + ("top",)}
    for p in range(period(traffic)):
        told = emissions(p, leaves, traffic)
        top = np.zeros(n_collectors, np.int64)
        np.maximum.at(top, wired, told[:, 3])
        agg["msgs"].append(count)
        for j in range(WIDTH):
            agg[f"sum{j}"].append(np.bincount(
                wired, weights=told[:, j], minlength=n_collectors
            ).astype(np.int64))
        agg["alarms"].append((top > 0).astype(np.int64))
        agg["peak_total"].append(top)
        agg["top"].append(top)
    return {k: np.stack(v) for k, v in agg.items()}


def after(t: int, leaves: dict, traffic: dict, n_collectors: int):
    """(collector state, inbox payload) after t steps from an empty system:
    the collectors have received what was told at steps 0 .. t-2, and the
    inbox holds what was told at step t-1 (None before the first step)."""
    agg = phase_aggregates(leaves, traffic, n_collectors)
    per = period(traffic)
    received = max(t - 1, 0)  # steps whose tells have been received
    times = received // per + (np.arange(per) < received % per)
    state = {k: (times[:, None] * agg[k]).sum(axis=0) for k in TOTALS}
    wired = np.bincount(leaves["collector"], minlength=n_collectors) > 0
    last = agg["top"][(received - 1) % per] if received else 0
    state["last_max"] = np.where(wired, last, 0).astype(np.int64)
    told = emissions(t - 1, leaves, traffic) if t >= 1 else None
    return state, told


def _message_keys(dst, payload, n_collectors: int, levels: int):
    """Each well-formed message as one integer (collector, then the four
    columns in base `levels`); `malformed` counts the others."""
    payload = np.asarray(payload, np.float64).reshape(-1, WIDTH)
    dst = np.asarray(dst, np.int64)
    finite = np.isfinite(payload).all(axis=1)
    cols = np.where(finite[:, None], payload, -1).astype(np.int64)
    fine = (dst >= 0) & (dst < n_collectors) & (cols == payload).all(axis=1) \
        & (cols >= 0).all(axis=1) & (cols < levels).all(axis=1)
    key = dst[fine]
    for j in range(WIDTH):
        key = key * levels + cols[fine, j]
    return key, int((~fine).sum())


def judge(t: int, leaves: dict, traffic: dict, n_collectors: int, got: dict,
          limits: dict, expected=None) -> dict:
    """Compare what the timed path left behind after t steps with the
    reference.

    `got`: `collectors` and `leaves` as dicts column -> array (the system's
    rows of each kind), `inbox_dst` / `inbox_payload` / `inbox_valid` as the
    system holds them (any layout: the valid messages are compared as a
    multiset), `dropped` (the device's own drop counters, summed).
    `expected`: what `after` returns for these arguments, for a caller that
    judges several outcomes of one run."""
    state, told = expected or after(t, leaves, traffic, n_collectors)
    wrong = np.zeros(n_collectors, bool)
    for k in COLLECTOR_COLUMNS:
        diff = np.asarray(got["collectors"][k], np.int64) - state[k]
        wrong |= diff % (1 << 32) != 0
    changed = np.zeros(leaves["collector"].shape[0], bool)
    for k in LEAF_COLUMNS:
        changed |= np.asarray(got["leaves"][k], np.int64) != leaves[k]

    levels = int(traffic["reading_levels"])
    valid = np.asarray(got["inbox_valid"], bool)
    have, malformed = _message_keys(
        np.asarray(got["inbox_dst"])[valid],
        np.asarray(got["inbox_payload"])[valid], n_collectors, levels)
    if told is None:
        want = np.zeros(0, np.int64)
    else:
        want, _ = _message_keys(leaves["collector"], told, n_collectors,
                                levels)
    bins = n_collectors * levels ** WIDTH
    tokens_wrong = malformed + int(np.abs(
        np.bincount(have, minlength=bins)
        - np.bincount(want, minlength=bins)).sum())
    numbers = {"collectors_wrong": int(wrong.sum()),
               "leaves_wrong": int(changed.sum()),
               "tokens_wrong": tokens_wrong,
               "messages_dropped": int(got["dropped"])}
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
