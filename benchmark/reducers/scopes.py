"""Device time by the program's own stage scopes.

The program runs every stage function under a ``jax.named_scope`` named
``srtb.<stage>`` (``srtb_tpu/ops/scopes.py``), so each device operation's
HLO ``op_name`` says which stage it belongs to: the INNERMOST ``srtb.``
component of the name.  A fusion is one operation and carries its root's
name.  What carries no such component is ``unscoped``.

The profiler keeps the ``op_name`` in the statistics of an operation's
event METADATA (``tf_op``), which ``jax.profiler.ProfileData`` of jaxlib
0.9.0 does not hand out (it lists an event's own statistics: offset and
duration).  So this file reads the ``.xplane.pb`` itself: the protobuf
wire format, the handful of fields named below, nothing imported.  It
looks at the same events as ``benchmark/trace.py``: the ``XLA Ops`` line
of every ``/device:TPU:<n>`` plane.  Where operations nest on that line
(a ``while`` around its body's operations) each one counts its own time
only, so the scopes add up to the busy union.

The harness leaves the slice under ``<checkout>/.bench_work/<cell>/trace``
until the result line is made; ``rec`` does not carry that path, so it is
found from ``benchmark.spec.CHECKOUT``.  A program without the scopes (an
earlier commit) has nothing to read: every reader then returns nothing.
"""

from __future__ import annotations

import glob
import os
import re

from benchmark import spec
from benchmark.trace import DEVICE_PLANE, OPS_LINE, short_name

# a component reads ``srtb.chirp``, or ``vmap(srtb.chirp)`` where the
# stage is traced under a transformation (the grid's trials, a batch plan)
SCOPE = re.compile(r"srtb\.[A-Za-z0-9_]+")
UNSCOPED = "unscoped"


# ------------------------------------------------------ protobuf, read only

def _fields(buf: memoryview):
    """(field number, value) of one message: an int for varints and fixed
    widths, a memoryview for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        number, wire = key >> 3, key & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield number, val
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield number, buf[i:i + size]
            i += size
        elif wire == 1:
            yield number, int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            yield number, int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


# XSpace.planes = 1; XPlane: name 2, lines 3, event_metadata 4 (map: key
# 1, value 2), stat_metadata 5 (map); XLine: name 2, events 4; XEvent:
# metadata_id 1, offset_ps 2, duration_ps 3; XEventMetadata: id 1, name 2,
# stats 5; XStat: metadata_id 1, str_value 5, ref_value 7 (the id of a
# stat_metadata whose name is the string); XStatMetadata: id 1, name 2.

def _plane(buf: memoryview, names: dict | None = None):
    """-> (name, {event metadata id: scope}, [(offset_ps, duration_ps,
    metadata id)] of the XLA Ops line).  ``names``, where given, is
    filled with {event metadata id: the operation's HLO text}."""
    name = ""
    lines, metas, stat_names = [], [], {}
    for number, val in _fields(buf):
        if number == 2:
            name = _text(val)
        elif number == 3:
            lines.append(val)
        elif number == 4:
            metas.append(val)
        elif number == 5:
            entry = dict(_fields(val))
            sm = dict(_fields(entry.get(2, memoryview(b""))))
            stat_names[sm.get(1, entry.get(1, 0))] = _text(sm.get(2, b""))
    if not DEVICE_PLANE.match(name):
        return name, {}, []
    scopes = {}
    for entry in metas:
        pair = dict(_fields(entry))
        meta_id, scope = pair.get(1, 0), UNSCOPED
        for number, val in _fields(pair.get(2, memoryview(b""))):
            if number == 1:
                meta_id = val
            elif number == 2 and names is not None:
                names[meta_id] = _text(val)
            elif number == 5:
                stat = dict(_fields(val))
                text = _text(stat[5]) if 5 in stat \
                    else stat_names.get(stat.get(7), "")
                found = SCOPE.findall(text)
                if found:
                    scope = found[-1]
        scopes[meta_id] = scope
    events = []
    for line in lines:
        evs, is_ops = [], False
        for number, val in _fields(line):
            if number == 2:
                is_ops = _text(val) == OPS_LINE
            elif number == 4:
                evs.append(val)
        if not is_ops:
            continue
        for ev in evs:
            f = dict(_fields(ev))
            events.append((f.get(2, 0), f.get(3, 0), f.get(1, 0)))
    return name, scopes, events


def _self_times(events: list):
    """[(offset, duration, id)] -> [(id, the operation's own picoseconds)]:
    what an operation's children on the same line took is theirs."""
    out, stack = [], []          # stack of [end, id, own]
    for off, dur, meta in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and off >= stack[-1][0]:
            end, m, own = stack.pop()
            out.append((m, own))
        if stack:
            stack[-1][2] -= dur
        stack.append([off + dur, meta, dur])
    out.extend((m, own) for _end, m, own in stack)
    return out


def scope_seconds(path: str) -> dict:
    """{scope: device seconds}, averaged over the device planes that ran
    any operation; {} where no operation carries a ``srtb.`` scope."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    total: dict = {}
    devices = 0
    for number, val in _fields(space):
        if number != 1:
            continue
        _name, scopes, events = _plane(val)
        if not events:
            continue
        devices += 1
        for meta, own in _self_times(events):
            scope = scopes.get(meta, UNSCOPED)
            total[scope] = total.get(scope, 0.0) + own * 1e-12
    if not devices or set(total) <= {UNSCOPED}:
        return {}
    return {k: v / devices for k, v in total.items()}


def op_scopes(path: str) -> dict:
    """{an operation's name as ``trace.short_name`` prints it: its
    scope}, over the operations that ran on any device plane: the lookup
    ``Trace.top_ops`` names its rows from.  {} where no operation
    carries a ``srtb.`` scope (a program without the scopes, the CPU)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for number, val in _fields(space):
        if number != 1:
            continue
        names: dict = {}
        _name, scopes, events = _plane(val, names)
        for meta in {m for _off, _dur, m in events}:
            out.setdefault(short_name(names.get(meta, "")),
                           scopes.get(meta, UNSCOPED))
    return {} if set(out.values()) <= {UNSCOPED} else out


# ------------------------------------------------------------- the reader

_CACHE: dict = {}


def slice_path() -> str | None:
    """The newest trace a run of this checkout has left."""
    paths = glob.glob(os.path.join(spec.CHECKOUT, ".bench_work", "*",
                                   "trace", "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def scope_ms_per_seg(rec, args):
    """Device milliseconds per segment of the slice, of the operations
    whose innermost ``srtb.`` scope is one of ``args.scopes``
    (``"unscoped"`` for those under none)."""
    tr = rec.trace
    if tr is None or not tr.devices or not tr.segments:
        return None
    path = slice_path()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = scope_seconds(path)
    by_scope = _CACHE[key]
    if not any(s in by_scope for s in args["scopes"]):
        return None       # no operation carries the scope: nothing to read
    return sum(by_scope.get(s, 0.0) for s in args["scopes"]) \
        / tr.segments * 1e3


REDUCERS = {"trace_scope_ms_per_seg": scope_ms_per_seg}
