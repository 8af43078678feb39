"""From a profiler trace to per-stage times, read from the program's own
spans and scopes.

The round loop opens host spans named ``fl.<stage>`` (the program's
``repro.engine.trace``), and the operations of the fused chunk and of
the compiled poll and training programs carry the named scopes ``poll``,
``select``, ``train`` and ``aggregate`` in their HLO metadata.  This
module builds on ``tracing`` and leaves what that reads and reduces as
it is:

1. ``read_stages(path)`` keeps what ``tracing.read_xplane`` keeps, plus
   ``program_spans``: the ``fl.*`` host spans, each ``[name, start_ns,
   duration_ns]``; and per device ``scoped``: each operation of a
   stage, as ``[stage, start_ns, duration_ns]``.
2. ``reduce_stages(events)`` is ``tracing.reduce_events(events)`` with
   ``program_spans`` (host seconds per span name), ``program_counts``
   (spans per name), ``program_self`` (seconds per name less the spans
   nested in it), ``scopes`` (device seconds per stage: the union of its
   operations' intervals, so that nested loops count once), and
   ``idle_by_label`` (the device's idle seconds by what the host had
   open, the benchmark's spans before the program's).  An idle gap
   whose middle no benchmark span covers is labelled by the ``fl.*``
   span open over most of it, not ``none``.  A trace without the
   program's spans reduces as ``tracing`` reduces it.
3. ``stage_metrics(reduced, rounds)``: the per-round numbers.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path

from benchlib import tracing

__all__ = ["PREFIX", "STAGES", "METRICS", "scope_stage", "read_stages",
           "reduce_stages", "stage_metrics"]

PREFIX = "fl."
STAGES = ("poll", "select", "train", "aggregate")
_STAGE_SET = frozenset(STAGES)

# metric -> (what it reads, name): the seconds of a span, its count, its
# self time, or the device seconds of a scope; each per round
METRICS = {
    "aggregate_ms": ("span", "aggregate"),
    "evaluate_ms": ("span", "evaluate"),
    "sync_wait_ms": ("span", "sync"),
    "syncs_per_round": ("count", "sync"),
    "unpack_ms": ("self", "unpack"),
    "poll_dev_ms": ("scope", "poll"),
    "train_dev_ms": ("scope", "train"),
    "select_dev_ms": ("scope", "select"),
    "aggregate_dev_ms": ("scope", "aggregate"),
}


def scope_stage(path: str) -> str | None:
    """The stage whose scope an ``op_name`` path holds (the innermost,
    where scopes nest), or ``None``."""
    for part in reversed(path.split("/")):
        if part in _STAGE_SET:
            return part
    return None


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: bytes):
    """The (number, value) fields of one protobuf message: an int for a
    varint, bytes for the rest."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        yield key >> 3, value


def _map_values(fields, number):
    """The values of the protobuf map field ``number``."""
    return [dict(_fields(entry)).get(2, b"") for n, entry in fields if n == number]


def _op_stages(path) -> dict:
    """Per device plane, the stage of each operation event name.  The
    named-scope path of an operation (its HLO ``op_name``) is the stat
    ``tf_op`` of the event's metadata, which ``ProfileData`` does not
    show, so the ``XSpace`` is read here: space = {1: planes}; plane =
    {2: name, 4: event metadata by id, 5: stat metadata by id}; event
    metadata = {2: name, 5: stats}; stat metadata = {1: id, 2: name};
    stat = {1: stat metadata id, 3 or 4: integer, 5: string, 7: id of a
    stat metadata whose name is the string}.  An operation with no stage
    in its path (a copy of an argument, a loop XLA adds) takes the stage
    of its program where all the program's other operations share one;
    a name whose operations lie in different stages is left out."""
    out = {}
    for num, plane in _fields(Path(path).read_bytes()):
        fields = list(_fields(plane)) if num == 1 else []
        name = next((v.decode() for n, v in fields if n == 2), "")
        if not name.startswith("/device:"):
            continue
        strings = {}
        for meta in _map_values(fields, 5):
            meta = dict(_fields(meta))
            strings[meta.get(1, 0)] = meta.get(2, b"").decode()
        ids = {v: k for k, v in strings.items()}
        ops = []  # (name, program, stage)
        for meta in _map_values(fields, 4):
            meta = list(_fields(meta))
            stats = [dict(_fields(v)) for n, v in meta if n == 5]
            where = program = None
            for stat in stats:
                if stat.get(1) == ids.get("tf_op"):
                    where = (stat[5].decode() if 5 in stat
                             else strings.get(stat.get(7), ""))
                elif stat.get(1) == ids.get("program_id"):
                    program = stat.get(3, stat.get(4))
            op = next((v.decode() for n, v in meta if n == 2), "")
            ops.append((op, program, scope_stage(where or "")))
        by_program = defaultdict(set)
        for _, program, stage in ops:
            if stage:
                by_program[program].add(stage)
        stages = {}
        for op, program, stage in ops:
            if stage is None and len(by_program[program]) == 1:
                (stage,) = by_program[program]
            if stage:
                stages[op] = stage if stages.get(op, stage) == stage else None
        out[name] = {op: stage for op, stage in stages.items() if stage}
    return out


def read_stages(path) -> dict:
    from jax.profiler import ProfileData

    events = tracing.read_xplane(path)
    op_stages = _op_stages(path)
    spans, scoped = [], {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[ev.name, ev.start_ns, ev.duration_ns]
                          for ev in line.events if ev.name.startswith(PREFIX)]
        elif plane.name in op_stages:
            stage_of = op_stages[plane.name]
            scoped[plane.name] = [
                [stage_of[ev.name], ev.start_ns, ev.duration_ns]
                for line in plane.lines if line.name == "XLA Ops"
                for ev in line.events if ev.name in stage_of]
    for dev in events["devices"]:
        dev["scoped"] = scoped.get(dev["name"], [])
    events["program_spans"] = spans
    return events


def _innermost(spans, t, prefix):
    """The shortest span open at ``t`` whose name starts with
    ``prefix``, or ``None``."""
    best = None
    for name, a, b in spans:
        if (name != tracing.WINDOW and name.startswith(prefix) and a <= t <= b
                and (best is None or b - a < best[1])):
            best = (name, b - a)
    return best[0] if best else None


def _label(spans, t) -> str:
    """The innermost benchmark span open at ``t`` (named as ``tracing``
    names it), else the innermost program span, else ``"none"``."""
    name = _innermost(spans, t, "bench.")
    if name:
        return name[len("bench."):]
    return _innermost(spans, t, PREFIX) or "none"


def _self_seconds(spans) -> dict:
    """Seconds per name less the spans nested directly in each."""
    out = defaultdict(float)
    for i, (name, a, b) in enumerate(spans):
        inner = [(n, x, y) for j, (n, x, y) in enumerate(spans)
                 if j != i and a <= x and y <= b and (y - x, j) < (b - a, i)]
        covered = sum(y - x for x, y in tracing._union(inner))
        out[name[len(PREFIX):]] += (b - a - covered) / 1e9
    return dict(out)


def _gap_shares(gaps, bench, program) -> list[dict]:
    """For each gap, its idle seconds by label: the gap cut at every
    span edge in it, each piece labelled by the spans open over it."""
    spans = bench + program
    edges = sorted({x for _, a, b in spans for x in (a, b)})
    starts = defaultdict(list)
    for span in spans:
        starts[span[1]].append(span)
    labels, active = [], []  # labels[k]: the piece edges[k]..edges[k + 1]
    for x, y in zip(edges, edges[1:]):
        active = [s for s in active if s[2] > x] + starts.get(x, [])
        labels.append(_label(active, (x + y) / 2))
    out = []
    for a, b in gaps:
        share = defaultdict(float)
        cuts = ([a] + edges[bisect.bisect_right(edges, a):bisect.bisect_left(edges, b)]
                + [b])
        for x, y in zip(cuts, cuts[1:]):
            k = bisect.bisect_right(edges, x) - 1
            share[labels[k] if 0 <= k < len(labels) else "none"] += (y - x) / 1e9
        out.append(share)
    return out


def _gap_label(bench, share, a, b) -> str:
    """The benchmark span open at the gap's middle, as ``tracing``
    labels it; else the program span open over most of the gap."""
    name = _innermost(bench, (a + b) / 2, "bench.")
    if name:
        return name[len("bench."):]
    program = {k: v for k, v in share.items() if k.startswith(PREFIX)}
    return max(program, key=program.get) if program else "none"


def reduce_stages(events: dict, top: int = 10) -> dict | None:
    red = tracing.reduce_events(events, top)
    program = events.get("program_spans")
    if red is None or program is None:
        return red
    _, t0, dur = [s for s in events["spans"] if s[0] == tracing.WINDOW][-1]
    t1 = t0 + dur
    bench = [s for s in tracing._clip(events["spans"], t0, t1)
             if s[0] != tracing.WINDOW]
    program = tracing._clip(program, t0, t1)
    seconds, counts = defaultdict(float), defaultdict(int)
    for name, a, b in program:
        seconds[name[len(PREFIX):]] += (b - a) / 1e9
        counts[name[len(PREFIX):]] += 1
    scopes = defaultdict(float)
    n_dev = len(events["devices"])
    for dev in events["devices"]:
        by_stage = defaultdict(list)
        for stage, a, b in tracing._clip(dev.get("scoped", []), t0, t1):
            by_stage[stage].append((stage, a, b))
        for stage, ops in by_stage.items():
            busy = sum(y - x for x, y in tracing._union(ops))
            scopes[stage] += busy / n_dev / 1e9
    dev0 = events["devices"][0]
    merged = tracing._union(tracing._clip(dev0["ops"] or dev0["modules"], t0, t1))
    edges = [t0] + [x for iv in merged for x in iv] + [t1]
    gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
            if edges[j + 1] > edges[j]]
    shares = _gap_shares(gaps, bench, program)
    longest = sorted(range(len(gaps)), key=lambda i: gaps[i][0] - gaps[i][1])[:top]
    red["breakdown"]["idle_gaps"] = [
        [_gap_label(bench, shares[i], *gaps[i]), (gaps[i][1] - gaps[i][0]) / 1e9]
        for i in longest]
    idle = defaultdict(float)
    for share in shares:
        for label, t in share.items():
            idle[label] += t
    red["program_spans"] = dict(seconds)
    red["program_counts"] = dict(counts)
    red["program_self"] = _self_seconds(program)
    red["scopes"] = dict(scopes)
    red["idle_by_label"] = dict(idle)
    return red


def stage_metrics(reduced: dict | None, rounds: int) -> dict:
    """Milliseconds (or spans) per round of each metric of ``METRICS``
    that finds something to read."""
    if not reduced or not rounds:
        return {}
    tables = {"span": reduced.get("program_spans"), "count": reduced.get("program_counts"),
              "self": reduced.get("program_self"), "scope": reduced.get("scopes")}
    out = {}
    for metric, (kind, name) in METRICS.items():
        value = (tables[kind] or {}).get(name)
        if value:
            out[metric] = value / rounds * (1 if kind == "count" else 1000.0)
    return out
