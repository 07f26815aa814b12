"""``dmgrid``: the DM-trial grid across the chips of one host, built the
way ``srtb_tpu/tools/main.py``'s ``--dm_list`` branch builds it —
``Config.from_args`` then ``DMSearchPipeline(cfg, source=...)``.

Its loop has no sinks: a segment is complete when its record is in
``<prefix>dm_trials.jsonl``, and that is where it is stamped, without an
edit to the program: the path the program appends to is a FIFO that the
benchmark made, and one thread of the benchmark (``RecordDrain``) reads
each line as it arrives, stamps the segment handed over longest ago and
appends the line to the real file beside it
(``<prefix>dm_trials.records.jsonl``).  So a loop that pulled segment
k+1 before k's record exists would still have k stamped at its record.
Worst-case lateness of the stamp: the thread is woken by the kernel when
the program's ``flush`` returns, but reads the clock only once it holds
the interpreter lock, which the loop's thread gives up at its next
blocking call (today the reader's ``file.read`` of the next pull, some
tenths of a millisecond on) or after the switch interval (5 ms) at the
latest (a traced run's profiler start or stop holds the lock for 0.2-1 s
and delays the stamps that fall into it).  In today's synchronous loop the
record's arrival and the next pull are the same moment to within that, and
every run prints the median and the largest difference between the two.
With ``--trace 1`` the same thread opens and closes the profiler's slice,
right after a stamp.

What the timed path hands out per segment is that record (per-trial peak
S/N and counts; the series and the peak bin never leave the chips), so
that is what is compared: EVERY trial's peak S/N with the float64 reference, so that each
chip's shard of the grid is held to it.  Two numbers, because the trials
answer differently: ``snr_gap`` over the matched trial and its two
neighbours (the pulse stands in them; the lower-precision controls fail
it), ``snr_gap_outer`` over the others (there the peak is the smeared
pulse or a noise peak, which lower precision hardly moves; it is held
against a shard that comes back zeroed, garbled or in another order).
"""

from __future__ import annotations

import json
import os
import statistics
import threading
import time

import numpy as np

from benchmark import check
from benchmark.harness import say


class RecordDrain:
    """Reads the FIFO at ``fifo``: every line is one segment's record.
    Stamps ``done`` on the segment at the head of ``handed`` and appends
    the line to ``real``.  The FIFO is opened for reading AND writing, so
    the program's ``open(..., "a")`` never waits for a reader and the end
    of one ``run()`` is no end of file for the next."""

    STOP = b'{"benchmark": "stop"}\n'

    def __init__(self, fifo: str, real: str, handed):
        self.real, self.handed = real, handed
        self.lines = 0
        self.tick = None          # called with every stamp, after it
        self.error = None
        os.mkfifo(fifo)
        self._fd = os.open(fifo, os.O_RDWR)
        self._thread = threading.Thread(target=self._drain, daemon=True,
                                        name="bench-record-drain")
        self._thread.start()

    def _drain(self) -> None:
        try:
            with open(self.real, "ab") as out:
                buf = b""
                while True:
                    buf += os.read(self._fd, 1 << 16)
                    now = time.perf_counter()
                    while b"\n" in buf:
                        line, _, buf = buf.partition(b"\n")
                        if line + b"\n" == self.STOP:
                            return
                        self.handed.popleft().done = now
                        if self.tick is not None:
                            self.tick(now)
                        out.write(line + b"\n")
                        out.flush()
                        self.lines += 1
        except Exception as e:  # the loop's thread must not block on a
            self.error = e      # full pipe: keep the read end emptied
            while os.read(self._fd, 1 << 16):
                pass

    def wait_for(self, lines: int, timeout: float = 10.0) -> None:
        """Until the records written so far have been stamped."""
        t_end = time.perf_counter() + timeout
        while self.lines < lines and self.error is None \
                and time.perf_counter() < t_end:
            time.sleep(0.0005)
        if self.error is not None or self.lines < lines:
            raise RuntimeError(f"the record drain has {self.lines} of "
                               f"{lines} records: {self.error!r}")

    def close(self) -> None:
        if self.error is None and self._thread.is_alive():
            os.write(self._fd, self.STOP)
        self._thread.join(timeout=10.0)
        os.close(self._fd)


def near_trials(dm_list: list, dm: float) -> list:
    """The matched trial and its two neighbours."""
    i = min(range(len(dm_list)), key=lambda j: abs(dm_list[j] - dm))
    return [j for j in (i - 1, i, i + 1) if 0 <= j < len(dm_list)]


def run(run, sources: dict) -> None:
    from srtb_tpu.config import Config
    from srtb_tpu.pipeline.runtime import DMSearchPipeline

    wl = run.spec.workload
    lay = run.lay
    cfg = Config.from_args(run.argv())
    dm_list = [float(d) for d in cfg.dm_list]
    pulse_dm = float(wl["pulses"]["dm"])
    near = near_trials(dm_list, pulse_dm)
    run.choose_sample()
    run.start_reference(lambda k: dm_list)
    source = sources[wl["source"]["kind"]](cfg, lay, run.rec, wl["source"])
    search = DMSearchPipeline(cfg, source=source)
    say(f"mesh: {dict(search.mesh.shape)}; {len(dm_list)} trials "
        f"{dm_list}, all compared; the pulse stands in trials {near}")
    real = run.prefix + "dm_trials.records.jsonl"
    drain = RecordDrain(search.trials_path, real, source.handed)
    try:
        source.begin("warmup")
        search.run()
        source.end_phase()
        drain.wait_for(len(run.rec.segs))
        say("warm-up done")
        ref = run.join_reference()
        run.open_window(source)
        # the profiler's slice opens and closes at a completion, on the
        # thread that stamps it: the segments counted in the slice are
        # then those whose device work lies in it, whichever thread wins
        # the interpreter lock at a pull
        drain.tick, source.tick = source.tick, None
        search.run()
        source.end_phase()
        drain.wait_for(len(run.rec.segs))
        run.close_window()
    finally:
        source.end_phase()
        drain.close()
    report_stamps(run.rec.segs)
    with open(real) as f:
        records = [json.loads(ln) for ln in f]
    if len(records) != len(run.rec.segs):
        raise RuntimeError(f"{len(records)} trial records for "
                           f"{len(run.rec.segs)} segments")
    for s, r in zip(run.rec.segs, records):
        s.trials = r
    judge(run, ref, dm_list, near, pulse_dm)


def report_stamps(segs: list) -> None:
    """On an earlier line of every run: the record's arrival against the
    loop's next pull, over every segment.  In a synchronous loop the two
    agree; a loop that overlaps steps would pull before the record."""
    diffs = sorted((s.done - s.next_pull) * 1e3 for s in segs
                   if s.done > 0.0 and s.next_pull > 0.0)
    if diffs:
        say(f"completion stamps: record's arrival minus the next pull over "
            f"{len(diffs)} segments, ms: median "
            f"{statistics.median(diffs):.3f}, smallest {diffs[0]:.3f}, "
            f"largest {diffs[-1]:.3f}, largest magnitude "
            f"{max(abs(d) for d in diffs):.3f}")


def judge(run, ref: dict, dm_list, near, pulse_dm: float) -> None:
    rec, ck = run.rec, run.checks
    thr = run.params["snr_threshold"]
    keep = set(run.reference_segments())
    compared = 0
    for s in rec.segs:
        r = s.trials
        tag = f"{s.phase} segment {s.index} (file segment {s.file_seg})"
        key = (s.phase, s.index)
        ck.require(s.done > 0.0, f"{tag} never completed", key)
        fired = sum(r["signal_counts"]) > 0
        if s.pulsed:
            ck.require(fired and r["best_dm"] == pulse_dm
                       and r["best_snr"] > thr,
                       f"{tag} holds a pulse at DM {pulse_dm}: best_dm "
                       f"{r['best_dm']} snr {r['best_snr']}", key)
        else:
            ck.require(not fired, f"{tag} holds no pulse but fired: "
                       f"{r['signal_counts']}", key)
        if s.file_seg in keep:
            got = r["peak_snr"]
            want = [float(np.max(ref[f"s{s.file_seg}.t{j}.snr_peaks"]))
                    for j in range(len(dm_list))]
            outer = [j for j in range(len(dm_list)) if j not in near]
            where = f"{s.phase}.{s.index}.file_seg{s.file_seg}"
            for name, js in (("snr_gap", near), ("snr_gap_outer", outer)):
                ck.number(f"{name}.{where}", check.relative_gap(
                    [got[j] for j in js] if len(got) == len(want) else [],
                    [want[j] for j in js]), name)
            compared += s.phase == "window"
    ck.require(compared > 0, "no sampled segment of the window was "
               "compared with the reference")
    say(f"compared with the reference: {compared} window segment(s) of "
        f"file segments {run.sampled}")


DRIVERS = {"dmgrid": run}
