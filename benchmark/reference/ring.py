"""Plain reference for the ring configurations: numpy only, nothing of the
program.

Semantics (BASELINE.json configs 2 and 5; Savina thread-ring generalised):
actor i, on a step in which its mailbox holds messages, adds their count to
`received` and tells the SUM of their payloads to actor (i + stride) mod n.
The traffic `ring-full` puts one token in every mailbox, so the tokens move as
a permutation and no two ever meet: after T steps every actor has received T
messages, and the token now in actor d's mailbox is the one that started at
(d - T * stride) mod n, its payload unchanged. `step` is the literal rule, for
any mailbox contents; `after` is the same rule applied T times through the
permutation's power, which is what a run of thousands of steps can afford.
tests/benchmark holds the two against each other."""

from __future__ import annotations

import numpy as np


def seed_payload(n: int, width: int, seed: int, traffic: dict) -> np.ndarray:
    """The tokens' payloads, from the seed: column 0 is 1.0 as the source
    config seeds it, the others small integers, so that every per-step sum
    the program's prefix-difference kernels form stays an exact f32 integer
    (docs/DELIVERY_KERNELS.md: below 2^24 over a whole step)."""
    rng = np.random.default_rng([int(seed), 0x52494E47])
    p = rng.integers(0, int(traffic["payload_max"]) + 1,
                     size=(n, width)).astype(np.float32)
    p[:, 0] = 1.0
    return p


def step(dst, payload, received, stride: int):
    """One literal step: deliver, reduce per destination, forward."""
    n = received.shape[0]
    count = np.bincount(dst, minlength=n)
    summed = np.zeros((n, payload.shape[1]), np.float64)
    np.add.at(summed, dst, payload)
    got = count > 0
    ids = np.nonzero(got)[0]
    return ((ids + stride) % n, summed[ids].astype(np.float32),
            received + count)


def power(next_of: np.ndarray, t: int) -> np.ndarray:
    """next_of composed with itself t times, by repeated squaring."""
    out = np.arange(next_of.shape[0])
    base = next_of
    while t:
        if t & 1:
            out = base[out]
        t >>= 1
        if t:
            base = base[base]
    return out


def after(n: int, stride: int, payload0: np.ndarray, t: int):
    """(received, payload_at) after t steps of the full ring: received[i]
    and the payload now waiting in actor i's mailbox."""
    where = power((np.arange(n) + stride) % n, t)  # token j sits at where[j]
    payload_at = np.empty_like(payload0)
    payload_at[where] = payload0
    return np.full((n,), t, np.int64), payload_at


def judge(n: int, stride: int, payload0: np.ndarray, t: int, got: dict,
          limits: dict) -> dict:
    """Compare what the timed path left behind with the reference.

    `got`: received [n], inbox_dst / inbox_payload / inbox_valid as the
    system holds them, dropped (the device's own drop counters, summed)."""
    received, payload_at = after(n, stride, payload0, t)
    rows_wrong = int((np.asarray(got["received"], np.int64) != received).sum())
    valid = np.asarray(got["inbox_valid"], bool)
    dst = np.asarray(got["inbox_dst"])[valid].astype(np.int64)
    pay = np.asarray(got["inbox_payload"])[valid]
    in_range = (dst >= 0) & (dst < n)
    seen = np.bincount(dst[in_range], minlength=n)
    # a mailbox that holds no token, or two, or one from somewhere else
    tokens_wrong = int((seen != 1).sum()) + int((~in_range).sum())
    ok = in_range & (seen[np.clip(dst, 0, n - 1)] == 1)
    tokens_wrong += int((pay[ok] != payload_at[dst[ok]]).any(axis=1).sum())
    numbers = {"rows_received_wrong": rows_wrong,
               "tokens_wrong": tokens_wrong,
               "messages_dropped": int(got["dropped"])}
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
