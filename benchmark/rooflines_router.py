"""The least bytes a step of the router pool must move, from the
configuration's shapes alone (as benchmark/rooflines.py counts the ring's):
never from the kernel that ran."""

from __future__ import annotations


def router_step_bytes(config: dict, chips: int = 1) -> float:
    """Bytes one chip must move in one step: every producer's state read (a
    producer reads its ref, mask and job and writes nothing back), every
    routee's state read and written, the router's row read and written, and
    for every TELLING producer (`producers / tell_one_in` of them a step,
    traffic `router-random`) one message written by its sender, its address
    read and rewritten by the route stage, and the message read by its
    receiver."""
    producers = config["producers"] / chips
    telling = producers / config["tell_one_in"]
    return (producers * config["state_bytes_per_producer"]
            + config["routees"] / chips * config["state_bytes_per_routee"] * 2
            + config["state_bytes_router"] * 2
            + telling * (config["message_bytes"] * 2 + 8))


def router_route_bytes(config: dict, chips: int = 1) -> float:
    """Bytes the route stage alone must move in one step: the address of
    every inbox row read and written (4 B + 4 B): the stage cannot know
    which rows hold a message for the router without reading each."""
    return config["inbox_rows"] / chips * 8
