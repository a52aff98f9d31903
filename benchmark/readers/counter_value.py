"""A number the cell's driver computed from the program's own counters and
left in `obs` under `key` (say `stash_share`, from `read_emit_stats()`
before and after the window). A program without those counters leaves
nothing there: nothing to read."""


def read(obs, key):
    value = obs.get(key)
    return None if value is None else float(value)
