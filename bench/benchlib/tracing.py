"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, so that the second can be checked on a trace recorded on the
chip and kept with the benchmark:

1. ``read_xplane(path)`` keeps, of an ``.xplane.pb``, the events that
   matter: per device plane the program events (line ``XLA Modules``)
   and the operation events (line ``XLA Ops``), and the host spans the
   benchmark opened (names starting ``bench.``), each as
   ``[name, start_ns, duration_ns]``.
2. ``reduce_events(events)`` cuts them to the traced window (the span
   ``bench.window``) and sums: busy seconds per device (the union of
   operation intervals), device seconds per jitted program (by its
   stable name: ``jit__poll_losses`` is ``_poll_losses``), host seconds
   per span, and the breakdown of the longest operations and idle gaps.
"""

from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict
from pathlib import Path

__all__ = ["find_xplane", "read_xplane", "reduce_events", "op_name",
           "program_name", "save_events", "load_events"]

WINDOW = "bench.window"
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")


def find_xplane(trace_dir) -> Path | None:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def op_name(event_name: str) -> str:
    """An operation's short name: on the TPU the event carries the whole
    HLO instruction (``%while.22 = (...) while(...)``); keep its name."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:80]


def program_name(module: str) -> str:
    """``jit__poll_losses(12)`` -> ``_poll_losses``: the name given to
    ``jax.jit``, which stays put across refactors of the program body."""
    base = _SUFFIX.sub("", module)
    return base[4:] if base.startswith("jit_") else base


def read_xplane(path) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key:
                    dev[key] += [[op_name(ev.name), ev.start_ns, ev.duration_ns]
                                 for ev in line.events]
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[ev.name, ev.start_ns, ev.duration_ns]
                          for ev in line.events if ev.name.startswith("bench.")]
    return {"devices": devices, "spans": spans}


def save_events(events: dict, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_events(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _clip(evs, t0, t1):
    out = []
    for name, s, d in evs:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b))
    return out


def _union(intervals):
    merged = []
    for _, a, b in sorted(intervals, key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _open_span(spans, t):
    """The innermost benchmark span open at ``t`` (the host's activity
    during a device gap), or ``"none"``."""
    best = None
    for name, a, b in spans:
        if name != WINDOW and a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0][len("bench."):] if best else "none"


def reduce_events(events: dict, top: int = 10) -> dict | None:
    """Window, busy time, program and span seconds, and the breakdown;
    ``None`` when the trace holds no window or no device."""
    windows = [s for s in events["spans"] if s[0] == WINDOW]
    if not windows or not events["devices"]:
        return None
    _, t0, dur = windows[-1]
    t1 = t0 + dur
    spans = _clip(events["spans"], t0, t1)
    busy, programs, op_time = [], defaultdict(float), defaultdict(float)
    gaps = []
    for i, dev in enumerate(events["devices"]):
        ops = _clip(dev["ops"] or dev["modules"], t0, t1)
        merged = _union(ops)
        busy.append(sum(b - a for a, b in merged))
        for name, a, b in _clip(dev["modules"], t0, t1):
            programs[program_name(name)] += (b - a) / len(events["devices"])
        if i == 0:
            for name, a, b in ops:
                op_time[name] += b - a
            edges = [t0] + [x for iv in merged for x in iv] + [t1]
            gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    span_s = defaultdict(float)
    for name, a, b in spans:
        if name != WINDOW:
            span_s[name[len("bench."):]] += (b - a) / 1e9
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": dur / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "programs": {k: v / 1e9 for k, v in programs.items()},
        "spans": dict(span_s),
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in
                           sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[_open_span(spans, (a + b) / 2), (b - a) / 1e9]
                          for a, b in gaps[:top]],
        },
    }
