"""The least work a step must do, from the configuration's shapes alone:
never from the kernel that ran, so the number means the same whatever
implements the step."""

from __future__ import annotations


def ring_step_bytes(config: dict, chips: int = 1) -> float:
    """Bytes one chip must move in one step of a full ring: every live
    actor's state read and written, every message written by its sender and
    read by its receiver. One message per actor per step (traffic
    `ring-full`). With several chips each holds actors / chips."""
    actors = config["actors"] / chips
    state = actors * config["state_bytes_per_actor"] * 2
    messages = actors * config["message_bytes"] * 2
    return state + messages


def roofline_share(least_bytes: float, seconds: float, peaks: dict) -> float:
    """Percent of the memory roofline: the time the chip's HBM needs for
    `least_bytes`, over the time taken. These steps do no matrix work, so
    bandwidth is the bound."""
    return 100.0 * (least_bytes / peaks["hbm_bytes_per_s"]) / seconds
