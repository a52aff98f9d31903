"""The least bytes a step of the bank must move, from the configuration's
shapes alone (as benchmark/rooflines.py counts the ring's): never from the
kernel that ran."""

from __future__ import annotations


def bank_step_bytes(config: dict, chips: int = 1) -> float:
    """Bytes one chip must move in one step: every teller's state read (a
    teller reads its rule and writes nothing back), every account's state
    read and written, and for every teller one message written by its
    sender, read by the enqueue, written to its mailbox slot and read there
    by the fold."""
    tellers = config["tellers"] / chips
    return (tellers * config["state_bytes_per_teller"]
            + config["accounts"] / chips * config["state_bytes_per_account"] * 2
            + tellers * (config["message_bytes"] + config["slot_bytes"]) * 2)


def bank_place_bytes(config: dict, chips: int = 1) -> float:
    """Bytes the enqueue alone must move in one step: each of the step's
    messages (one a teller) read where the sort left it and written to its
    mailbox slot. What is carried over is a thousandth of that and is not
    counted."""
    return config["tellers"] / chips * (config["message_bytes"]
                                       + config["slot_bytes"])
