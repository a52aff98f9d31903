"""The least bytes a step of the sharded bank-transfer cell must move on one
chip, from the configuration's shapes alone (as benchmark/rooflines_xbank.py
and rooflines_transfer.py count theirs): never from the kernel that ran. The
accounts and tellers are spread evenly, so every chip is the fullest."""

from __future__ import annotations

from benchmark.rooflines_xbank import EXCHANGE_PASSES


def xxfer_exchange_bytes(config: dict, chips: int = 4) -> float:
    """Bytes the exchange alone must move on one chip in one step: each
    message the chip emits (`messages_a_step`, the draw's steady count, a
    chip's share) four times, as rooflines_xbank.py counts the passes."""
    return config["messages_a_step"] / chips * config["message_bytes"] \
        * EXCHANGE_PASSES


def xxfer_step_bytes(config: dict, chips: int = 4) -> float:
    """Bytes one chip must move in one step: every account's state read and
    written, every teller's read and its three counters written, every
    emission row's key read by the bucketing, every receive row's key read
    by the delivery, and each of the chip's handled messages written by its
    sender, carried through the exchange's four passes, read by the enqueue,
    written to its mailbox slot and read there by the fold."""
    accounts, tellers = config["accounts"] / chips, config["tellers"] / chips
    messages = config["messages_a_step"] / chips
    return (accounts * config["state_bytes_per_account"] * 2
            + tellers * (config["state_bytes_per_teller"] + 12)
            + config["emission_rows_per_chip"] * 4
            + config["inbox_rows_per_chip"] * 4
            + messages * (config["message_bytes"] * 2
                          + config["slot_bytes"] * 2)
            + xxfer_exchange_bytes(config, chips))
