"""The least bytes a step of the sharded bank must move on one chip, from the
configuration's shapes alone (as benchmark/rooflines_bank.py counts the
one-chip bank's): never from the kernel that ran. The tellers are spread
evenly, so every chip is the fullest."""

from __future__ import annotations

# the passes a message makes that lie under `akka.exchange`: read by the
# bucketing, written to the send buffer, read and written by the receive
EXCHANGE_PASSES = 4


def xbank_exchange_bytes(config: dict, chips: int = 4) -> float:
    """Bytes the exchange alone must move on one chip in one step: each of
    the chip's tellers' messages four times."""
    return config["tellers"] / chips * config["message_bytes"] \
        * EXCHANGE_PASSES


def xbank_step_bytes(config: dict, chips: int = 4) -> float:
    """Bytes one chip must move in one step: every teller's state read,
    every account's state read and written, and for every teller one
    message written by the emit, carried through the exchange, read by the
    enqueue, written to its mailbox slot and read there by the fold."""
    tellers = config["tellers"] / chips
    return (tellers * config["state_bytes_per_teller"]
            + config["accounts"] / chips
            * config["state_bytes_per_account"] * 2
            + tellers * (config["message_bytes"] + config["slot_bytes"]) * 2
            + xbank_exchange_bytes(config, chips))
