"""Plain reference for the router pool: numpy only, nothing of the program.

Semantics (BASELINE.json config 4; akka.routing.RoundRobinPool, whose logic
is ONE counter a router, `next.getAndIncrement % size`, run where the message
is sent: the router's mailbox is not on the path). Producer i holds the ref
of the router, a mask of P = `mask_period` bits and three integers a_i, b_i,
size_i below `levels`. At step t, the system's own step counter at the start
of the step (0 for the first), it tells the router

    [1, a_i, b_i, size_i]    iff bit (t mod P) of mask_i is set.

A message told at step t is routed and received at step t + 1. The router
holds `next` (0 at the start) and `routed`. The m messages a step brings it
are numbered k = 0 .. m-1 IN INBOX ROW ORDER: the producers' emission slots
by producer row, then whatever the host told; message k takes the sequence
number s = next + k and goes to routee

    round-robin:  s mod n            random:  fnv1a(s) mod n

then next <- (next + m) mod n and routed += m. A routee that receives c > 0
messages at a step adds c to `hits`, columns 1..3 to `sum1..sum3`, and with
`top` the largest column 3 among them `peak_total += top`, `last_hits = c`,
`last_max = top`; one that receives none does not run. Totals are int32 in
the system and wrap: they are compared modulo 2^32.

Where the stage runs decides what a window leaves in the inbox. It runs at
the HEAD of the step that delivers (akka_tpu StepCore.route, ahead of
deliver), so that host tells and emissions are ranked together; the inbox a
run leaves therefore holds the last step's tells still addressed to the
ROUTER's ref, not yet numbered. `judge` compares it so.

`step` is the literal rule. `after` reaches a window's end inside the run's
time, for round-robin: `hits` in closed form from the total count; the sums
by folding each of the P phases' message lists modulo n once (`fold`) and
adding the fold rotated by `next` for every step of that phase
(`accumulate`: some 8,000 rotations of an n-vector, not 8,000 x 524,288
messages). For `random` it is the literal rule, step by step: test sizes
only. tests/benchmark holds the two against each other."""

from __future__ import annotations

import numpy as np

PRODUCER_COLUMNS = ("router", "mask", "a", "b", "size")
TOTALS = ("hits", "sum1", "sum2", "sum3", "peak_total")
ROUTEE_COLUMNS = TOTALS + ("last_hits", "last_max")
ROUTER_COLUMNS = ("next", "routed")
LOGICS = ("round-robin", "random")
WIDTH = 4


def seed_producers(n_producers: int, router: int, seed: int,
                   traffic: dict) -> dict:
    """The deployment's producers, from the seed: the router's ref, a mask
    of P bits each set with probability 1 / `tell_one_in`, and a, b, size in
    [0, levels). Small integers, so that every per-step column total stays
    below 2^24 and the program's prefix-difference sums are exact f32
    (docs/DELIVERY_KERNELS.md)."""
    rng = np.random.default_rng([int(seed), 0x524F5554])
    period, levels = int(traffic["mask_period"]), int(traffic["levels"])
    bits = rng.integers(0, int(traffic["tell_one_in"]),
                        (n_producers, period), dtype=np.uint8) == 0
    mask = (bits.astype(np.int32) << np.arange(period, dtype=np.int32)
            ).sum(axis=1, dtype=np.int64)
    return {"router": np.full(n_producers, int(router), np.int64),
            "mask": mask,
            "a": rng.integers(0, levels, n_producers),
            "b": rng.integers(0, levels, n_producers),
            "size": rng.integers(0, levels, n_producers)}


def tells(t: int, producers: dict, traffic: dict) -> np.ndarray:
    """[n_producers] bool: who tells at step t."""
    return (producers["mask"] >> (t % int(traffic["mask_period"]))) & 1 == 1


def emissions(t: int, producers: dict, traffic: dict) -> np.ndarray:
    """[m, 4] int64: what the router is told at step t, in producer order."""
    who = tells(t, producers, traffic)
    return np.stack([np.ones(int(who.sum()), np.int64), producers["a"][who],
                     producers["b"][who], producers["size"][who]], axis=1)


def _fnv1a(x: np.ndarray) -> np.ndarray:
    """The 32-bit FNV-1a mix of the four bytes of each uint32."""
    x = x.astype(np.uint64)
    h = np.full(x.shape, 2166136261, np.uint64)
    for shift in (0, 8, 16, 24):
        h = ((h ^ ((x >> shift) & 0xFF)) * 16777619) & 0xFFFFFFFF
    return h


def index_of(seq: np.ndarray, n: int, logic: str) -> np.ndarray:
    """The routee of each sequence number (taken modulo 2^32)."""
    seq = np.asarray(seq, np.uint64) & 0xFFFFFFFF
    if logic == "round-robin":
        return (seq % n).astype(np.int64)
    if logic == "random":
        return (_fnv1a(seq) % n).astype(np.int64)
    raise ValueError(f"unknown routing logic {logic!r}; one of {LOGICS}")


def zero_state(n_routees: int) -> tuple:
    return ({k: np.zeros(n_routees, np.int64) for k in ROUTEE_COLUMNS},
            dict.fromkeys(ROUTER_COLUMNS, 0))


def received(dst: np.ndarray, told: np.ndarray, n: int) -> dict:
    """What n routees get from the messages `told` sent to `dst`: count,
    sums of columns 1..3 and the largest column 3, each [n]."""
    top = np.zeros(n, np.int64)
    np.maximum.at(top, dst, told[:, 3])
    got = {"count": np.bincount(dst, minlength=n), "top": top}
    for j in (1, 2, 3):
        got[f"sum{j}"] = np.bincount(dst, weights=told[:, j],
                                     minlength=n).astype(np.int64)
    return got


def _receive(state: dict, got: dict) -> dict:
    some = got["count"] > 0
    new = {"hits": state["hits"] + got["count"],
           "peak_total": state["peak_total"] + got["top"],
           "last_hits": np.where(some, got["count"], state["last_hits"]),
           "last_max": np.where(some, got["top"], state["last_max"])}
    for j in (1, 2, 3):
        new[f"sum{j}"] = state[f"sum{j}"] + got[f"sum{j}"]
    return new


def step(t: int, inbox: np.ndarray, routees: dict, router: dict,
         producers: dict, traffic: dict, logic: str):
    """One literal step: the router's `inbox` ([m, 4], in row order) is
    numbered, routed and received, the producers tell step t's messages.
    Returns (next inbox, routees, router). A caller with host tells appends
    them to the inbox it passes: the host's rows come last."""
    n = routees["hits"].shape[0]
    m = inbox.shape[0]
    dst = index_of(router["next"] + np.arange(m), n, logic)
    routees = _receive(routees, received(dst, inbox, n))
    router = {"next": (router["next"] + m) % n, "routed": router["routed"] + m}
    return emissions(t, producers, traffic), routees, router


# ------------------------------------------------- the window's end, folded
def fold(keys: np.ndarray, told: np.ndarray, n: int) -> dict:
    """`received` of the messages keyed modulo n: what n routees would get
    were message i sent to `keys[i] mod n`; a step's share is this rotated."""
    return received(np.asarray(keys, np.int64) % n, told, n)


def phase_folds(producers: dict, traffic: dict, n: int, by_sender=None):
    """Per phase p of the mask's period: the fold of the messages told at a
    step t = p (mod P), keyed by their rank in producer order (or, for a
    control, by `by_sender`, one key a producer), and their number."""
    folds, counts = [], []
    for p in range(int(traffic["mask_period"])):
        told = emissions(p, producers, traffic)
        keys = np.arange(told.shape[0]) if by_sender is None \
            else by_sender[tells(p, producers, traffic)]
        folds.append(fold(keys, told, n))
        counts.append(told.shape[0])
    return folds, counts


def accumulate(folds: list, rotations: np.ndarray, n: int) -> dict:
    """The routees' columns after the steps s = 0 .. len(rotations)-1, step
    s bringing routee (j + rotations[s]) mod n what `folds[s mod P]` holds
    at j. `last_*` come from the last step that brought a routee anything."""
    period = len(folds)
    twice = [{k: np.concatenate([v, v]) for k, v in f.items()} for f in folds]

    def rotated(s: int, k: str) -> np.ndarray:
        r = int(rotations[s]) % n
        return twice[s % period][k][n - r:2 * n - r]

    names = {"hits": "count", "sum1": "sum1", "sum2": "sum2", "sum3": "sum3",
             "peak_total": "top"}
    state = {k: np.zeros(n, np.int64) for k in ROUTEE_COLUMNS}
    for s in range(len(rotations)):
        for col, k in names.items():
            state[col] += rotated(s, k)
    missing = np.ones(n, bool)
    for s in range(len(rotations) - 1, -1, -1):
        count = rotated(s, "count")
        fresh = missing & (count > 0)
        state["last_hits"][fresh] = count[fresh]
        state["last_max"][fresh] = rotated(s, "top")[fresh]
        missing &= ~fresh
        if not missing.any():
            break
    return state


def after(t: int, producers: dict, traffic: dict, n_routees: int,
          logic: str = "round-robin"):
    """(routees, router, told) after t steps from an empty system: the pool
    has routed what was told at steps 0 .. t-2, and the inbox holds what was
    told at step t-1, `told` ([m, 4]; None before the first step)."""
    n = n_routees
    told = emissions(t - 1, producers, traffic) if t >= 1 else None
    if logic == "random":  # the literal rule; no fold rotates a hash
        routees, router = zero_state(n)
        inbox = np.zeros((0, WIDTH), np.int64)
        for s in range(t):
            inbox, routees, router = step(s, inbox, routees, router,
                                          producers, traffic, logic)
        return routees, router, told
    if logic != "round-robin":
        raise ValueError(f"unknown routing logic {logic!r}; one of {LOGICS}")
    folds, counts = phase_folds(producers, traffic, n)
    routed_steps = max(t - 1, 0)
    per_step = np.asarray(counts, np.int64)[
        np.arange(routed_steps) % len(counts)]
    before = np.concatenate([[0], np.cumsum(per_step)])  # routed before s
    total = int(before[-1])
    routees = accumulate(folds, before[:-1] % n, n)
    # the closed form: sequence numbers 0 .. total-1, number s to s mod n
    hits = total // n + (np.arange(n) < total % n)
    if not (routees["hits"] == hits).all():
        raise AssertionError("the folded count disagrees with total // n")
    return routees, {"next": total % n, "routed": total}, told


# ------------------------------------------------------------ the verdict
def _message_keys(dst, payload, router: int, levels: int):
    """Each well-formed message (addressed to the router's ref, column 0
    equal to 1, columns 1..3 integers below `levels`) as one integer;
    `malformed` counts the others."""
    payload = np.asarray(payload, np.float64).reshape(-1, WIDTH)
    dst = np.asarray(dst, np.int64)
    finite = np.isfinite(payload).all(axis=1)
    cols = np.where(finite[:, None], payload, -1).astype(np.int64)
    fine = (dst == router) & (cols == payload).all(axis=1) \
        & (cols[:, 0] == 1) & (cols >= 0).all(axis=1) \
        & (cols < levels).all(axis=1)
    key = np.zeros(int(fine.sum()), np.int64)
    for j in (1, 2, 3):
        key = key * levels + cols[fine, j]
    return key, int((~fine).sum())


def judge(t: int, producers: dict, traffic: dict, n_routees: int, logic: str,
          got: dict, limits: dict, expected=None) -> dict:
    """Compare what the timed path left behind after t steps with the
    reference.

    `got`: `routees` and `producers` as dicts column -> array (the system's
    rows of each kind), `router` as {"next", "routed"}, `inbox_dst` /
    `inbox_payload` / `inbox_valid` as the system holds them (any layout:
    the valid messages are compared as a multiset), `dropped` (the device's
    own drop counters, summed). `expected`: what `after` returns for these
    arguments, for a caller that judges several outcomes of one run.

    `balance_over_one` is the pool's own guarantee, read off the system's
    `hits` alone: by how much the largest load exceeds the smallest plus
    one, under round-robin (0 under `random`, which promises no balance)."""
    routees, router, told = expected or after(t, producers, traffic,
                                              n_routees, logic)
    wrong = np.zeros(n_routees, bool)
    for k in ROUTEE_COLUMNS:
        diff = np.asarray(got["routees"][k], np.int64) - routees[k]
        wrong |= diff % (1 << 32) != 0
    hits = np.asarray(got["routees"]["hits"], np.int64)
    apart = (hits - hits[0] + (1 << 31)) % (1 << 32) - (1 << 31)  # wrap-proof
    over = max(int(apart.max() - apart.min()) - 1, 0) \
        if logic == "round-robin" else 0
    counter = sum((int(got["router"][k]) - router[k]) % (1 << 32) != 0
                  for k in ROUTER_COLUMNS)
    changed = np.zeros(producers["router"].shape[0], bool)
    for k in PRODUCER_COLUMNS:
        changed |= np.asarray(got["producers"][k], np.int64) != producers[k]

    levels = int(traffic["levels"])
    ref = int(producers["router"][0])
    valid = np.asarray(got["inbox_valid"], bool)
    have, malformed = _message_keys(
        np.asarray(got["inbox_dst"])[valid],
        np.asarray(got["inbox_payload"])[valid], ref, levels)
    if told is None:
        want = np.zeros(0, np.int64)
    else:
        want, _ = _message_keys(np.full(told.shape[0], ref), told, ref, levels)
    bins = levels ** 3
    tokens_wrong = malformed + int(np.abs(
        np.bincount(have, minlength=bins)
        - np.bincount(want, minlength=bins)).sum())
    numbers = {"routees_wrong": int(wrong.sum()),
               "balance_over_one": over,
               "router_counter_wrong": int(counter),
               "producers_wrong": int(changed.sum()),
               "tokens_wrong": tokens_wrong,
               "messages_dropped": int(got["dropped"])}
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
