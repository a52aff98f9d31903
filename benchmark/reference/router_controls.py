"""The router pool's controls: the reference put in the program's place with
ONE of the configuration's guarantees broken. Each has to come out as not
correct, by the limits named for it here (`CAUGHT_BY`) and no other, or the
comparison that decides `correct` proves nothing. The configuration states no
numeric precision: every number compared is exact, with the limit 0.

A fault in where a message goes shows twice, and that is meant: in
`routees_wrong` (a routee's columns differ from the reference's) and, where
it unbalances the pool by more than one, in `balance_over_one`, which is
read off the system's own `hits` with no reference at all. So a lost or a
doubled tell, or one sent to the neighbouring routee, MUST trip
`routees_wrong` and MAY trip `balance_over_one` (it does when the routee it
hits stood at the low, or the high, end of the pool's two load levels); the
two faults of the rule itself must trip both.

`benchmark/tools/control_router.py` runs them on the chip at the cell's own
size, after a real window, on the steps that window ran; tests/benchmark
runs them at toy sizes."""

from __future__ import annotations

import numpy as np

from benchmark.reference import router as ref


def reference_outcome(t: int, producers: dict, traffic: dict, n_routees: int,
                      logic: str, expected=None) -> dict:
    """What a faultless system leaves behind after t steps (`expected`:
    `ref.after` of the same arguments, where the caller has it)."""
    routees, router, told = expected or ref.after(t, producers, traffic,
                                                  n_routees, logic)
    if told is None:
        told = np.zeros((0, ref.WIDTH), np.int64)
    m = told.shape[0]
    return {"routees": {k: v.copy() for k, v in routees.items()},
            "router": dict(router),
            "producers": {k: producers[k].copy()
                          for k in ref.PRODUCER_COLUMNS},
            "inbox_dst": np.full(m, int(producers["router"][0]), np.int64),
            "inbox_payload": told.astype(np.float32),
            "inbox_valid": np.ones(m, bool),
            "dropped": 0}


class Case:
    """What a breaker may read: the run it breaks the outcome of."""

    def __init__(self, t, producers, traffic, n_routees, logic):
        self.t, self.producers, self.traffic = t, producers, traffic
        self.n, self.logic = n_routees, logic
        self.routed_steps = max(t - 1, 0)
        if self.routed_steps < 2:
            raise ValueError("the controls need a run of three steps or more")
        if logic != "round-robin":
            raise ValueError("the controls break the round-robin rule")
        # the first message of the first step went to routee 0
        self.first = ref.emissions(0, producers, traffic)[0]

    def rerun(self, rotations, by_sender=None) -> dict:
        """The routees' columns had every step s brought routee
        (j + rotations[s]) mod n the fold's row j."""
        folds, _ = ref.phase_folds(self.producers, self.traffic, self.n,
                                   by_sender)
        return ref.accumulate(folds, rotations, self.n)


def _take(got, routee: int, told, sign: int) -> None:
    got["routees"]["hits"][routee] += sign
    for j in (1, 2, 3):
        got["routees"][f"sum{j}"][routee] += sign * int(told[j])


def tell_lost(got, case):
    _take(got, 0, case.first, -1)


def tell_delivered_twice(got, case):
    _take(got, 0, case.first, +1)


def tell_to_the_neighbouring_routee(got, case):
    _take(got, 0, case.first, -1)
    _take(got, 1 % case.n, case.first, +1)


def tell_lost_in_flight(got, case):
    got["inbox_valid"][got["inbox_valid"].shape[0] // 3] = False


def counter_not_advanced(got, case):
    """Every step starts where the first started: `next` stays 0."""
    got["routees"] = case.rerun(np.zeros(case.routed_steps, np.int64))
    got["router"]["next"] = 0


def per_sender_round_robin(got, case):
    """`(id + t) mod n` a sender in place of the pool's one counter (what
    this repo's builders computed before the route stage): each routee's
    load is then a sum of coin flips, not a share of the count."""
    ids = np.arange(case.producers["router"].shape[0])
    got["routees"] = case.rerun(np.arange(case.routed_steps), by_sender=ids)


def max_of_the_step_before(got, case):
    stale = ref.after(case.t - 1, case.producers, case.traffic, case.n,
                      case.logic)[0]["last_max"]
    if (stale == got["routees"]["last_max"]).all():
        raise ValueError("the last two steps' maxima agree everywhere")
    got["routees"]["last_max"] = stale


def drop_counted(got, case):
    got["dropped"] = 1


def producer_rewired(got, case):
    i = got["producers"]["router"].shape[0] // 3
    got["producers"]["router"][i] += 1


CONTROLS = {f.__name__: f for f in (
    tell_lost, tell_delivered_twice, tell_to_the_neighbouring_routee,
    tell_lost_in_flight, counter_not_advanced, per_sender_round_robin,
    max_of_the_step_before, drop_counted, producer_rewired)}

# control -> (the limits it must trip, those it may trip besides)
CAUGHT_BY = {
    "tell_lost": ({"routees_wrong"}, {"balance_over_one"}),
    "tell_delivered_twice": ({"routees_wrong"}, {"balance_over_one"}),
    "tell_to_the_neighbouring_routee": ({"routees_wrong"},
                                        {"balance_over_one"}),
    "tell_lost_in_flight": ({"tokens_wrong"}, set()),
    "counter_not_advanced": ({"routees_wrong", "router_counter_wrong",
                              "balance_over_one"}, set()),
    "per_sender_round_robin": ({"routees_wrong", "balance_over_one"}, set()),
    "max_of_the_step_before": ({"routees_wrong"}, set()),
    "drop_counted": ({"messages_dropped"}, set()),
    "producer_rewired": ({"producers_wrong"}, set()),
}


def judge_controls(t, producers, traffic, n_routees, logic, limits) -> dict:
    """Every control, and the unbroken reference in the program's place
    (`reference_itself`, the one that has to come out correct)."""
    case = Case(t, producers, traffic, n_routees, logic)
    expected = ref.after(t, producers, traffic, n_routees, logic)  # once
    out = {}
    for name, breaker in dict(CONTROLS,
                              reference_itself=lambda got, case: None).items():
        got = reference_outcome(t, producers, traffic, n_routees, logic,
                                expected)
        breaker(got, case)
        out[name] = ref.judge(t, producers, traffic, n_routees, logic, got,
                              limits, expected)
    return out


def caught_as_named(name: str, numbers: dict) -> bool:
    """Did the control come out not correct by its limits and no other?"""
    wrong = {k for k, c in numbers.items() if c["value"] > c["limit"]}
    must, may = CAUGHT_BY[name]
    return must <= wrong <= must | may
