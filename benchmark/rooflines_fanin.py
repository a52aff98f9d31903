"""The least bytes a step of the fan-in aggregator must move, from the
configuration's shapes alone (as benchmark/rooflines.py counts the ring's):
never from the kernel that ran."""

from __future__ import annotations


def fanin_step_bytes(config: dict, chips: int = 1) -> float:
    """Bytes one chip must move in one step: every leaf's state read (a leaf
    reads its ref and readings and writes nothing back), every collector's
    state read and written, and one message per leaf written by its sender
    and read by its receiver (traffic `fanin-tick`). With several chips each
    holds its share of both kinds."""
    leaves = config["leaves"] / chips
    collectors = config["collectors"] / chips
    return (leaves * config["state_bytes_per_leaf"]
            + collectors * config["state_bytes_per_collector"] * 2
            + leaves * config["message_bytes"] * 2)
