"""Share of its roofline that ``fused_gather_aggregate`` reached in the
window, in percent: the least time of its calls (each the larger of its
bytes over HBM bandwidth and its operations over peak, ``counts.py``,
from the live edges of the window's mini-batches) over its device time.
Bytes bound it."""


def read(w):
    if w.trace is None or w.gather_least_s is None:
        return None
    s = w.trace["kernel_s"].get("fused_gather_aggregate", 0.0)
    return 100.0 * w.gather_least_s / s if s > 0 else None
