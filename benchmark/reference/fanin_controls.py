"""The fan-in aggregator's controls: the reference put in the program's
place with ONE of the configuration's guarantees broken. Each has to come out
as not correct, or the comparison that decides `correct` proves nothing. The
configuration states no numeric precision: every number compared is exact,
with the limit 0.

`benchmark/tools/control.py` runs them on the chip at the cell's own size,
after a real window, on the steps that window ran; tests/benchmark runs them
at toy sizes."""

from __future__ import annotations

import numpy as np

from benchmark.reference import fanin as ref


def reference_outcome(t: int, leaves: dict, traffic: dict,
                      n_collectors: int, expected=None) -> dict:
    """What a faultless system leaves behind after t steps (`expected`:
    `ref.after` of the same arguments, where the caller has it)."""
    state, told = expected or ref.after(t, leaves, traffic, n_collectors)
    n = leaves["collector"].shape[0]
    if told is None:
        told = np.zeros((n, ref.WIDTH), np.int64)
    return {"collectors": {k: v.copy() for k, v in state.items()},
            "leaves": {k: leaves[k].copy() for k in ref.LEAF_COLUMNS},
            "inbox_dst": leaves["collector"].copy(),
            "inbox_payload": told.astype(np.float32),
            "inbox_valid": np.full(n, t >= 1),
            "dropped": 0}


class Case:
    """What a breaker may read: the run it breaks the outcome of."""

    def __init__(self, t, leaves, traffic, n_collectors):
        self.t, self.leaves, self.traffic = t, leaves, traffic
        self.n_collectors = n_collectors
        self.received = max(t - 1, 0)
        self.leaf = leaves["collector"].shape[0] // 3  # the one leaf touched
        if self.received < 2:
            raise ValueError("the controls need a run of three steps or more")

    def top_at(self, step: int) -> np.ndarray:
        """Per collector, the largest alarm among the tells of `step`."""
        top = np.zeros(self.n_collectors, np.int64)
        np.maximum.at(top, self.leaves["collector"],
                      ref.emissions(step, self.leaves, self.traffic)[:, 3])
        return top


def tell_lost(got, case):
    got["inbox_valid"][case.leaf] = False


def tell_delivered_twice(got, case):
    c = case.leaves["collector"][case.leaf]
    told = ref.emissions(0, case.leaves, case.traffic)[case.leaf]
    got["collectors"]["msgs"][c] += 1
    for j in range(ref.WIDTH):
        got["collectors"][f"sum{j}"][c] += told[j]


def reading_to_the_neighbour(got, case):
    got["inbox_dst"][case.leaf] = (got["inbox_dst"][case.leaf] + 1) \
        % case.n_collectors


def alarm_missed_by_the_max(got, case):
    for step in range(min(case.received, ref.period(case.traffic))):
        top = case.top_at(step)
        if top.any():
            c = int(np.argmax(top))
            got["collectors"]["alarms"][c] -= 1
            got["collectors"]["peak_total"][c] -= top[c]
            return
    raise ValueError("no alarm was told in the steps of this run")


def max_of_the_step_before(got, case):
    last, before = case.top_at(case.received - 1), case.top_at(
        case.received - 2)
    if (last == before).all():
        raise ValueError("the last two steps' maxima agree everywhere")
    got["collectors"]["last_max"] = before


def drop_counted(got, case):
    got["dropped"] = 1


def leaf_rewired(got, case):
    got["leaves"]["collector"][case.leaf] = \
        (got["leaves"]["collector"][case.leaf] + 1) % case.n_collectors


CONTROLS = {f.__name__: f for f in (
    tell_lost, tell_delivered_twice, reading_to_the_neighbour,
    alarm_missed_by_the_max, max_of_the_step_before, drop_counted,
    leaf_rewired)}


def judge_controls(t, leaves, traffic, n_collectors, limits) -> dict:
    """Every control, and the unbroken reference in the program's place
    (`reference_itself`, the one that has to come out correct)."""
    case = Case(t, leaves, traffic, n_collectors)
    expected = ref.after(t, leaves, traffic, n_collectors)  # once for all
    out = {}
    for name, breaker in dict(CONTROLS,
                              reference_itself=lambda got, case: None).items():
        got = reference_outcome(t, leaves, traffic, n_collectors, expected)
        breaker(got, case)
        out[name] = ref.judge(t, leaves, traffic, n_collectors, got, limits,
                              expected)
    return out
