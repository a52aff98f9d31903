"""The controls: the reference put in the program's place with ONE of the
configuration's guarantees broken. Each has to come out as not correct, or
the comparison that decides `correct` proves nothing. These systems state no
numeric precision, so a control breaks a guarantee (delivery exactly once, no
drop) and every number compared is exact, with the limit 0.

`benchmark/tools/control.py` runs them on the chip at a cell's own size,
after a real window, on the steps that window ran; tests/benchmark runs them
at toy sizes."""

from __future__ import annotations

import numpy as np

from benchmark.reference import ring as ring_ref


# ------------------------------------------------------------------ ring
def ring_reference_outcome(n: int, stride: int, payload0, t: int) -> dict:
    """What a faultless system leaves behind after t steps."""
    received, at = ring_ref.after(n, stride, payload0, t)
    return {"received": received, "inbox_dst": np.arange(n),
            "inbox_payload": at, "inbox_valid": np.ones(n, bool),
            "dropped": 0}


def ring_token_lost(got):
    got["inbox_valid"][len(got["inbox_valid"]) // 3] = False


def ring_token_delivered_twice(got):
    i = len(got["inbox_dst"]) // 3
    got["inbox_dst"][i] = got["inbox_dst"][i + 1]


def ring_token_altered(got):
    got["inbox_payload"][len(got["inbox_payload"]) // 3, 2] += 1


def ring_receive_not_counted(got):
    got["received"][len(got["received"]) // 3] -= 1


def ring_message_dropped(got):
    got["dropped"] = 1


RING = {f.__name__: f for f in (
    ring_token_lost, ring_token_delivered_twice, ring_token_altered,
    ring_receive_not_counted, ring_message_dropped)}


def judge_ring_controls(n, stride, payload0, t, limits) -> dict:
    """Every control, and the unbroken reference in the program's place
    (`reference_itself`, the one that has to come out correct)."""
    out = {}
    for name, breaker in dict(RING, reference_itself=lambda got: None).items():
        got = ring_reference_outcome(n, stride, payload0, t)
        breaker(got)
        out[name] = ring_ref.judge(n, stride, payload0, t, got, limits)
    return out
