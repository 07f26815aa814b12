"""From a profiler trace to numbers.

The harness opens ``jax.profiler`` itself around a few seconds in the
middle of the window (``Tracer``), then reads the ``.xplane.pb`` it wrote
with ``jax.profiler.ProfileData`` (``Trace.load``).  Only what has a
stable name is reduced: the union of the intervals in which any operation
ran on a device (busy), operations by HLO name (``fft``, ``all-reduce``,
``collective-permute``, ...), and the host's ``srtb:<stage>`` annotations,
which the program writes around ingest, dispatch, fetch and sink and which
share the device's clock: the longest idle gaps are attributed to them.

``selftest/test_trace.py`` holds these reductions to known numbers on a
small recorded trace kept beside it.
"""

from __future__ import annotations

import glob
import os
import re
import time

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# the line of a device plane that holds one event per HLO operation
OPS_LINE = "XLA Ops"
HOST_PLANE = re.compile(r"^/host:CPU$")


class Tracer:
    """Starts the profiler ``start_at`` seconds into the window and stops
    it ``slice_s`` later; driven by the source's pull of each segment,
    on the thread that runs the program's loop, or (the grid's driver) by
    each completion, on the thread that stamps it."""

    def __init__(self, out_dir: str, t0: float, start_at: float,
                 slice_s: float):
        self.out_dir = out_dir
        self.t_on_due = t0 + start_at
        self.slice_s = slice_s
        self.state = "idle"
        self.t_on = self.t_off = 0.0
        self.stop_cost_s = 0.0

    def tick(self, now: float) -> None:
        import jax

        if self.state == "idle" and now >= self.t_on_due:
            jax.profiler.start_trace(self.out_dir)
            self.t_on = time.perf_counter()
            self.state = "on"
        elif self.state == "on" and now >= self.t_on + self.slice_s:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "on":
            self.t_off = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_cost_s = time.perf_counter() - self.t_off
            self.state = "done"


def union_seconds(intervals: list) -> tuple:
    """[(start, end)] -> (seconds covered, [(gap_start, gap_end)])."""
    busy = 0.0
    gaps = []
    end = None
    for a, b in sorted(intervals):
        if end is None:
            busy, end = b - a, b
        elif a > end:
            gaps.append((end, a))
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    return busy, gaps


def short_name(event_name: str) -> str:
    """The profiler names a device operation by its whole HLO
    instruction (``%fusion.43 = (f32[128,...]{...}) fusion(...)``); keep
    the instruction's name and the shape it makes."""
    name, _, rest = event_name.partition(" = ")
    name = name.lstrip("%")
    shape = rest.lstrip("(").split("{")[0].split(" ")[0]
    return f"{name} {shape}".strip()[:80]


class Trace:
    """Device operations and host annotations of one traced slice;
    times in seconds on the profiler's clock."""

    def __init__(self, devices: dict, host: list, window_s: float):
        self.devices = devices      # {id: [(name, start, dur)]}
        self.host = host            # [(name, start, dur)]
        self.window_s = window_s    # length of the traced slice
        self.segments = 0           # segments completed inside it
        # {operation name: its ``srtb.`` scope or "unscoped"}, from
        # reducers/scopes.op_scopes; {} = the program names no stage
        self.scopes: dict = {}

    @staticmethod
    def newest(trace_dir: str) -> str:
        """The ``.xplane.pb`` the profiler left under ``trace_dir``."""
        paths = sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True))
        if not paths:
            raise RuntimeError(f"the profiler left no trace in {trace_dir}")
        return paths[-1]

    @classmethod
    def load(cls, trace_dir: str, window_s: float) -> "Trace":
        from jax.profiler import ProfileData

        return cls.from_profile(
            ProfileData.from_file(cls.newest(trace_dir)), window_s)

    @classmethod
    def from_profile(cls, data, window_s: float) -> "Trace":
        devices: dict = {}
        host: list = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                ops = devices.setdefault(int(m.group(1)), [])
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for ev in line.events:
                        ops.append((short_name(ev.name),
                                    ev.start_ns * 1e-9,
                                    ev.duration_ns * 1e-9))
            elif HOST_PLANE.match(plane.name):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(("srtb:", "bench:")):
                            host.append((ev.name, ev.start_ns * 1e-9,
                                         ev.duration_ns * 1e-9))
        return cls(devices, host, window_s)

    # ------------------------------------------------------- reductions

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices
        that ran any."""
        per = [union_seconds([(s, s + d) for _, s, d in ops])[0]
               for ops in self.devices.values() if ops]
        return sum(per) / len(per) if per else 0.0

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the operations whose name matches, averaged
        over the devices that ran any operation."""
        rx = re.compile(pattern)
        per = [sum(d for name, _, d in ops if rx.search(name))
               for ops in self.devices.values() if ops]
        return sum(per) / len(per) if per else 0.0

    def top_ops(self, k: int = 10) -> list:
        """[[name, seconds]]: the operations that took most device time
        (summed by name, averaged over devices), each named
        ``<scope>/<operation>`` where the program names its stages
        (``srtb.fft_r2c/fusion.43 f32[...]``, ``unscoped/copy.252 ...``)."""
        total: dict = {}
        n_dev = sum(1 for ops in self.devices.values() if ops) or 1
        for ops in self.devices.values():
            for name, _, d in ops:
                total[name] = total.get(name, 0.0) + d
        rows = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        scoped = self.scopes
        return [[f"{scoped.get(name, 'unscoped')}/{name}" if scoped else name,
                 sec / n_dev] for name, sec in rows]

    def idle_gaps(self, k: int = 10) -> list:
        """[[what the host was doing, seconds]]: the first device's idle
        gaps, each given to the host annotation that covers most of it,
        summed by annotation, longest first."""
        dev = next((ops for _, ops in sorted(self.devices.items())
                    if ops), [])
        _, gaps = union_seconds([(s, s + d) for _, s, d in dev])
        by: dict = {}
        for a, b in gaps:
            best, cover = "no_annotation", 0.0
            for name, s, d in self.host:
                c = min(b, s + d) - max(a, s)
                if c > cover:
                    best, cover = name, c
            by[best] = by.get(best, 0.0) + (b - a)
        return [[n, s] for n, s in sorted(by.items(),
                                          key=lambda kv: -kv[1])[:k]]
