"""Device time by jitted program.

A plan of several programs a segment (the staged plan: ``_stage_a``,
``_stage_b``, ``_stage_c``) tells them apart in the trace by itself: every
operation's HLO ``op_name`` begins ``jit(<program>)/...``, and the device
plane's ``XLA Modules`` line holds one event per launch, named
``jit_<program>(<fingerprint>)``.  An operation belongs to the program
at the head of its ``op_name``; one the compiler made itself (a layout
copy has no ``op_name``) belongs to the launch it ran inside.  Each
operation counts its own time only (``scopes._self_times``) and is first
cut to the slice (``scopes._cut``), as the scopes' sums are, so the
programs add up to the busy union as the scopes do.

A trace whose operations name no ``jit(`` program (a recorded trace from
before the names, the CPU) has nothing to read: the reader returns
nothing.
"""

from __future__ import annotations

import bisect
import os
import re

from benchmark.reducers.scopes import (_cut, _fields, _self_times, _text,
                                       slice_path)
from benchmark.trace import DEVICE_PLANE, OPS_LINE

MODULES_LINE = "XLA Modules"
OP_PROGRAM = re.compile(r"^jit\(([A-Za-z0-9_]+)\)")
MODULE_PROGRAM = re.compile(r"^jit_([A-Za-z0-9_]+?)(?:\(\d+\))?$")


def _plane(buf: memoryview):
    """-> ({event metadata id: program or None}, operations, launches):
    ``[(start_ps, duration_ps, metadata id)]`` of the ``XLA Ops`` line and
    ``[(start_ps, end_ps, program)]`` of the ``XLA Modules`` line; all
    empty for a plane that is no device."""
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
        return {}, [], []
    programs, meta_names = {}, {}
    for entry in metas:
        pair = dict(_fields(entry))
        meta_id, program = pair.get(1, 0), None
        for number, val in _fields(pair.get(2, memoryview(b""))):
            if number == 1:
                meta_id = val
            elif number == 2:
                meta_names[meta_id] = _text(val)
            elif number == 5:
                stat = dict(_fields(val))
                text = _text(stat[5]) if 5 in stat \
                    else stat_names.get(stat.get(7), "")
                found = OP_PROGRAM.match(text)
                if found:
                    program = found.group(1)
        programs[meta_id] = program
    ops, launches = [], []
    for line in lines:
        evs, line_name, t0_ps = [], "", 0
        for number, val in _fields(line):
            if number == 2:
                line_name = _text(val)
            elif number == 3:
                t0_ps = val * 1000
            elif number == 4:
                evs.append(val)
        if line_name not in (OPS_LINE, MODULES_LINE):
            continue
        for ev in evs:
            f = dict(_fields(ev))
            start, dur, meta = t0_ps + f.get(2, 0), f.get(3, 0), f.get(1, 0)
            if line_name == OPS_LINE:
                ops.append((start, dur, meta))
                continue
            found = MODULE_PROGRAM.match(meta_names.get(meta, ""))
            if found:
                launches.append((start, start + dur, found.group(1)))
    return programs, ops, sorted(launches)


def _launch_of(launches: list, starts: list, at_ps: int):
    """The program whose launch holds ``at_ps``; None outside any."""
    i = bisect.bisect_right(starts, at_ps) - 1
    if i >= 0 and at_ps < launches[i][1]:
        return launches[i][2]
    return None


def program_seconds(path: str, ends_ps: tuple | None = None) -> dict:
    """{program: device seconds} inside the slice, averaged over the
    device planes that ran any operation there; ``None`` keys what no
    program claims.  {} where no operation names a program."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    total: dict = {}
    devices = 0
    for number, val in _fields(space):
        if number != 1:
            continue
        programs, ops, launches = _plane(val)
        ops = _cut(ops, ends_ps)
        if not ops:
            continue
        devices += 1
        starts = [a for a, _b, _p in launches]
        # a nameless operation is its launch's: jit_<program> of the
        # module line and jit(<program>) of an op_name spell it alike
        named = [(start, dur, programs.get(meta)
                  or _launch_of(launches, starts, start))
                 for start, dur, meta in ops]
        for program, own in _self_times(named):
            total[program] = total.get(program, 0.0) + own * 1e-12
    if not devices or set(total) <= {None}:
        return {}
    return {k: v / devices for k, v in total.items()}


_CACHE: dict = {}


def program_ms_per_seg(rec, args):
    """Device milliseconds per segment of the slice, of the operations
    of the jitted programs named in ``args.programs``."""
    tr = rec.trace
    if tr is None or not tr.devices or not tr.segments:
        return None
    path = slice_path()
    if path is None:
        return None
    ends_ps = tr.ends_ns and tuple(int(t) * 1000 for t in tr.ends_ns)
    key = (path, os.path.getmtime(path), ends_ps)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = program_seconds(path, ends_ps)
    by_program = _CACHE[key]
    if not any(p in by_program for p in args["programs"]):
        return None       # the plan has no such program: nothing to read
    return sum(by_program.get(p, 0.0) for p in args["programs"]) \
        / tr.segments * 1e3


REDUCERS = {"trace_program_ms_per_seg": program_ms_per_seg}
