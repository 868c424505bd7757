"""Megabytes (1e6 B) the transport moved from remote KVStore owners per
training step in the window: a count from ``sampling_stats()``."""


def read(w):
    before, after = w.transport
    if w.steps <= 0:
        return None
    return (after["remote_bytes"] - before["remote_bytes"]) / w.steps / 1e6
