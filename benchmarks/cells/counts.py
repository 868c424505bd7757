"""Operations and bytes, counted from shapes, and the table of peaks.

``fused_gather_aggregate`` sums, for each live edge, one source row into
its destination row; with per-head edge weights (GAT's attention tail)
it scales each head's lanes first.  What the algorithm must move is each
live edge's source row and its two indices, the weights where there are
any, and the whole output array, which has the op's static row count.
Dead (padded) edge slots need neither a row nor an addition.  The least
time of a call is the larger of its bytes over the chip's HBM bandwidth
and its operations over its peak; the kernel's roofline share is the sum
of that over the calls, over the kernel's measured time.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
F32 = 4


def peaks(device_kind: str) -> dict:
    """The row of ``peaks.json`` for this device; a device that is not
    in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device {device_kind!r} in "
                         f"peaks.json; have {sorted(table)}")
    return table[device_kind]


def gather_bytes(call: dict) -> float:
    e, w, h = call["edges"], call["width"], call["heads"]
    return float(e * (w * F32 + 2 * 4 + h * F32) + call["out_rows"] * w * F32)


def gather_flops(call: dict) -> float:
    return float(call["edges"] * call["width"] * (2 if call["heads"] else 1))


def least_seconds(calls: list, peak: dict) -> float:
    return sum(max(gather_bytes(c) / peak["hbm_bytes_per_s"],
                   gather_flops(c) / peak["flops_per_s"]) for c in calls)
