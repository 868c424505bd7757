"""Share of the traced window, in percent, in which no operation ran on
the device (``xplane.py``)."""


def read(w):
    if w.trace is None or w.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - w.trace["busy_s"] / w.trace["window_s"])
