"""The least bytes the bank-transfer cell must move, from the
configuration's shapes alone (as benchmark/rooflines_bank.py counts the
bank's): never from the kernel that ran."""

from __future__ import annotations


def xfer_step_bytes(config: dict, chips: int = 1) -> float:
    """Bytes one step must move: every account's state read and written,
    every teller's read and its three counters written, every inbox row's
    key read once by the delivery, and each of the step's handled messages
    (`messages_a_step`, the draw's steady count) written by its sender, read
    by the enqueue, written to its mailbox slot and read there by the
    fold."""
    accounts, tellers = config["accounts"] / chips, config["tellers"] / chips
    return (accounts * config["state_bytes_per_account"] * 2
            + tellers * (config["state_bytes_per_teller"] + 12)
            + config["inbox_rows"] / chips * 4
            + config["messages_a_step"] / chips
            * (config["message_bytes"] + config["slot_bytes"]) * 2)


def xfer_emit_bytes(config: dict, chips: int = 1) -> float:
    """Bytes the emission block alone must write in a step: the packed
    emission rows of the inbox, `emit_width` an account and `out_degree` a
    teller, a message's bytes each (`akka.emit.slots` writes every row,
    sent or empty)."""
    rows = (config["accounts"] * config["emit_width"]
            + config["tellers"] * config["out_degree"])
    return rows / chips * config["message_bytes"]
