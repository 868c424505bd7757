"""Milliseconds per training step that the training loop sat blocked
for its input in the window: each loader's consumer wait (the
``consumer`` entry of its public ``stats_report()["stages"]``), summed
over the loaders, over the window's steps.  Low while staging binds a
step; it rises when the loaders set the pace.  None where the loaders
keep no consumer counter."""

STAGE = "consumer"


def read(w):
    before, after = w.stages
    if w.steps <= 0 or not any(STAGE in a for a in after):
        return None
    wait = sum(a[STAGE]["wait_in_s"] - b.get(STAGE, {}).get("wait_in_s", 0.0)
               for b, a in zip(before, after) if STAGE in a)
    return 1e3 * wait / w.steps
