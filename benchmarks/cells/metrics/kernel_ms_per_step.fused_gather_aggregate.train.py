"""Device milliseconds of ``fused_gather_aggregate`` per step: every
call (forward, its backward, GAT's weighted forward), from the trace."""


def read(w):
    if w.trace is None or w.steps <= 0:
        return None
    s = w.trace["kernel_s"].get("fused_gather_aggregate", 0.0)
    return 1e3 * s / w.steps if s > 0 else None
