"""Milliseconds the loaders' ``sample`` stage was busy per mini-batch in
the window, over all loaders (their public ``stats_report()``)."""

STAGE = "sample"


def read(w):
    before, after = w.stages
    busy = items = 0.0
    for b, a in zip(before, after):
        if STAGE in a:
            busy += a[STAGE]["busy_s"] - b.get(STAGE, {}).get("busy_s", 0.0)
            items += a[STAGE]["items"] - b.get(STAGE, {}).get("items", 0)
    return 1e3 * busy / items if items > 0 else None
