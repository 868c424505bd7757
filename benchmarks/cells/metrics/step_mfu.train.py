"""Model FLOPs of the window's steps (forward and backward at the
configuration's padded capacities, ``models/<arch>.py``) over the
window's seconds and the chip's peak (``peaks.json``), in percent."""


def read(w):
    if w.steps <= 0 or not w.peak:
        return None
    return 100.0 * w.flops_per_step * w.steps / (w.seconds
                                                  * w.peak["flops_per_s"])
