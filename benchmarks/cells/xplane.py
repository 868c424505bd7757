"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics and the ``breakdown`` read.

The window is the host span the harness puts around it
(``benchmark_window``).  On each device plane (``/device:TPU:<n>``) the
operations are the events of its ``XLA Ops`` line, named by
:func:`op_name`; a device is busy where at least one runs, so busy time
is the union of their intervals inside the window, and the idle gaps are
the rest.  Host-to-device copies are not operations there: a batch in
flight to the device leaves it idle.  Each gap is named after the host
span that covers most of it, other than the harness's own window and
epoch spans, where one covers at least half of it.  A kernel's time is the summed duration of the device
operations whose name, or whose ``hlo_op`` or ``long_name``, starts with
the kernel's name (a Pallas kernel's operation carries the name given to
``pallas_call``).

:func:`summarize` takes plain event tuples, so that a test can drive it
with a small recorded trace in JSON (:func:`load_json`).
"""
from __future__ import annotations

import json
from collections import defaultdict
from typing import Iterable, Optional

WINDOW_SPAN = "benchmark_window"
EPOCH_SPAN = "train_epoch"
OWN_SPANS = (WINDOW_SPAN, EPOCH_SPAN)
# a gap that no host span covers by half: the host was in code that has
# no span of its own (inside ``train_epoch``: the loaders, ``_stack``)
UNSPANNED = "train_epoch (no finer span)"


def op_name(text: str) -> str:
    """``%fusion.15 = s32[6073344]{0:T(1024)} fusion(...)``, as a TPU
    trace names an operation, becomes ``fusion.15 s32[6073344]``."""
    if not text.startswith("%") or " = " not in text:
        return text
    name, rest = text[1:].split(" = ", 1)
    return f"{name} {rest.split('{', 1)[0].split(' ', 1)[0]}"


def _event_tuple(line: str, ev) -> tuple:
    stats = {}
    for k, v in ev.stats:
        if k in ("hlo_op", "long_name", "tf_op"):
            stats[k] = str(v)
    return (line, op_name(ev.name), float(ev.start_ns), float(ev.duration_ns),
            stats)


def load_xplane(path: str) -> dict:
    """``{"device": {plane: [events]}, "host": [events]}`` from a trace;
    an event is ``(line, name, start_ns, duration_ns, stats)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"device": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs.extend(_event_tuple(line.name, e) for e in line.events)
            out["device"][plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(_event_tuple(line.name, e)
                                   for e in line.events
                                   if e.duration_ns > 0)
    return out


def load_json(path: str) -> dict:
    with open(path) as f:
        raw = json.load(f)
    return {"device": {k: [tuple(e) for e in v]
                       for k, v in raw["device"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def _union(intervals: Iterable[tuple]) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _window(host: list) -> Optional[tuple]:
    spans = [(s, s + d) for _, name, s, d, _ in host if name == WINDOW_SPAN]
    return max(spans, key=lambda x: x[1] - x[0]) if spans else None


def _gap_name(host: list, s: float, e: float) -> str:
    """The host span that covers most of the gap, where one covers at
    least half of it; else :data:`UNSPANNED`."""
    best, best_overlap = UNSPANNED, 0.5 * (e - s)
    for _, name, hs, hd, _ in host:
        if name in OWN_SPANS:
            continue
        overlap = min(e, hs + hd) - max(s, hs)
        if overlap >= best_overlap:
            best, best_overlap = name, overlap
    return best


def matches(ev: tuple, kernel: str) -> bool:
    _, name, _, _, stats = ev
    return any(str(x).startswith(kernel)
               for x in (name, stats.get("hlo_op", ""),
                         stats.get("long_name", "")))


def summarize(trace: dict, kernels: Iterable[str] = (), top: int = 10
              ) -> Optional[dict]:
    """Busy and window seconds (busy averaged over the device planes that
    ran anything), each kernel's summed device seconds, the ``top``
    device operations by summed seconds and the ``top`` longest idle
    gaps.  None where the trace has no window span or no device op."""
    win = _window(trace["host"])
    planes = {k: v for k, v in trace["device"].items() if v}
    if win is None or not planes:
        return None
    w0, w1 = win
    busy, ops, gaps = [], defaultdict(float), []
    kernel_s = {k: 0.0 for k in kernels}
    for evs in planes.values():
        inside = [ev for ev in evs if ev[2] < w1 and ev[2] + ev[3] > w0]
        merged = _union((max(ev[2], w0), min(ev[2] + ev[3], w1))
                        for ev in inside)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for ev in inside:
            ops[ev[1]] += ev[3] * 1e-9
            for k in kernel_s:
                if matches(ev, k):
                    kernel_s[k] += ev[3] * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n = len(planes)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy) / n,
        "window_s": (w1 - w0) * 1e-9,
        "kernel_s": {k: v / n for k, v in kernel_s.items()},
        "device_ops": sorted(([k, v / n] for k, v in ops.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[_gap_name(trace["host"], s, e), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }
