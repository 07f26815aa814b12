"""``served``: the single-chip served path, built the way
``srtb_tpu/tools/main.py`` builds it — ``Config.from_args`` then
``Pipeline(cfg, source=...)`` — with the program's own sinks, and one
benchmark-owned sink appended last (as ``tools/main.py`` appends its GUI
tap) that stamps completion and keeps what the comparison needs.

Warm-up and window are two ``run()`` calls on the SAME ``Pipeline``: the
programs the warm-up compiled are the ones the window drives.
"""

from __future__ import annotations

import glob
import json
import os
import time

import numpy as np

from benchmark import check
from benchmark.harness import remove_file, say
from benchmark.reference import chain


class Stamp:
    """The last sink: the segment's results have reached every sink
    before it.  Completion is stamped here, after the candidate's files
    are closed where there is one."""

    def __init__(self, run, source, pipe):
        self.run, self.source, self.pipe = run, source, pipe
        self.keep = set(run.reference_segments())

    def push(self, work, has_signal: bool) -> None:
        rec = self.source.handed.popleft()
        if rec.segment is not work.segment:
            raise RuntimeError("sinks saw segments out of hand-over order")
        rec.segment = None
        rec.fired = bool(has_signal)
        det = work.detect
        rec.detections = int(np.asarray(det.signal_counts).sum())
        if has_signal:
            for sink in self.pipe.sinks:
                drain = getattr(sink, "drain", None)
                if drain is not None:
                    drain()       # the candidate's files are closed
        rec.done = time.perf_counter()
        if rec.file_seg in self.keep:
            rec.series = np.array(det.time_series, dtype=np.float32,
                                  copy=True)
            rec.snr_peaks = np.array(det.snr_peaks, dtype=np.float32,
                                     copy=True)
        if has_signal:
            self._candidate(rec)

    def _candidate(self, rec) -> None:
        """Verify and remove what the writers left for this segment."""
        lay = self.run.lay
        files = sorted(glob.glob(self.run.prefix + "*"))
        bins = [p for p in files if p.endswith(".bin")]
        tims = [p for p in files if p.endswith(".tim")]
        ok = (len(bins) == 1 and tims
              and os.path.getsize(bins[0]) == lay.segment_bytes)
        rec.trials = {"files": len(files), "files_ok": bool(ok)}
        for path in files:
            remove_file(path)


def run(run, sources: dict) -> None:
    from srtb_tpu.config import Config
    from srtb_tpu.ops import dedisperse as dd
    from srtb_tpu.pipeline.runtime import Pipeline

    wl = run.spec.workload
    lay = run.lay
    cfg = Config.from_args(
        run.argv(f"--telemetry_journal_path={run.journal}"))
    if int(dd.nsamps_reserved(cfg)) != lay.reserved:
        raise RuntimeError(
            f"the program reserves {dd.nsamps_reserved(cfg)} samples, the "
            f"benchmark's layout {lay.reserved}")
    run.choose_sample()
    source = sources[wl["source"]["kind"]](cfg, lay, run.rec, wl["source"])
    t0 = time.perf_counter()
    pipe = Pipeline(cfg, source=source)
    say(f"Pipeline constructed in {time.perf_counter() - t0:.2f} s")
    # the reference starts only now: construction is one host thread of
    # float64 (the chirp bank), and a child on every core beside it made
    # setup_s measure the benchmark's own load (PERF.md 6)
    run.start_reference(lambda k: [run.params["dm"]])
    pipe.sinks.append(Stamp(run, source, pipe))
    say(f"plan: {getattr(pipe.processor, 'plan_name', '?')}; sinks "
        f"{[type(s).__name__ for s in pipe.sinks]}")
    try:
        source.begin("warmup")
        pipe.run()
        say("warm-up done")
        ref = run.join_reference()
        run.open_window(source)
        pipe.run()
        run.close_window()
    finally:
        pipe.close()
        source.end_phase()
    spans = []
    if os.path.exists(run.journal):
        with open(run.journal) as f:
            spans = [json.loads(ln) for ln in f]
        spans = [s for s in spans if s.get("type") == "segment_span"]
    run.rec.warm_spans = spans[:lay.n_warmup]
    run.rec.spans = spans[lay.n_warmup:]
    report_journal(run.rec.spans)
    note_h2d_state(run)
    judge(run, ref)


def report_journal(spans: list) -> None:
    """The window's host stages as the program journals them (median,
    95th percentile, longest): on an earlier line of every run, so that
    a run that reads slow says where."""
    rows = []
    series = {stage: [s["stages_ms"][stage] for s in spans
                      if stage in s.get("stages_ms", {})]
              for stage in ("ingest", "dispatch", "fetch", "sink")}
    series["device_ms"] = [s["device_ms"] for s in spans
                           if "device_ms" in s]
    for name, vals in series.items():
        vals.sort()
        if vals:
            rows.append(f"{name} {vals[len(vals) // 2]:.2f}/"
                        f"{vals[int(0.95 * (len(vals) - 1))]:.2f}/"
                        f"{vals[-1]:.2f}")
    cold = spans[-1].get("ring_cold_dispatches", 0) \
        - spans[0].get("ring_cold_dispatches", 0) if spans else 0
    say(f"journal, window, ms median/p95/max: {', '.join(rows)}; cold ring "
        f"dispatches {cold}")


# between the two states' dispatch medians (1.4-1.8 and 5.5-6.1 ms)
H2D_STATE_SPLIT_MS = 3.5


def note_h2d_state(run) -> None:
    """Which of the served path's two states this process ran in (PERF.md
    6): "A" where every dispatch stages its 27.7 MB on the loop thread,
    "B" where most hand it over and return.  A note on the result line,
    not a metric: a later PR can condition its readings on it."""
    vals = sorted(s["stages_ms"]["dispatch"] for s in run.rec.spans
                  if "dispatch" in s.get("stages_ms", {}))
    if vals:
        med = vals[len(vals) // 2]
        run.notes["dispatch_ms_median"] = med
        run.notes["h2d_state"] = "A" if med > H2D_STATE_SPLIT_MS else "B"
        say(f"served path state {run.notes['h2d_state']} (dispatch median "
            f"{med:.2f} ms)")


def judge(run, ref: dict) -> None:
    """The schedule on every segment, the reference on the warm-up pulse
    and on the sampled segments of the window."""
    lay, rec, ck = run.lay, run.rec, run.checks
    done = [s for s in rec.segs if s.done > 0.0]
    ck.require(len(done) == len(rec.segs),
               f"{len(rec.segs) - len(done)} segment(s) handed over never "
               "reached the sinks")
    window = rec.window()
    n_spans = len(rec.spans)
    ck.require(n_spans == len(window),
               f"{n_spans} journal spans for {len(window)} window segments")
    for s in done:
        tag = f"{s.phase} segment {s.index} (file segment {s.file_seg})"
        key = (s.phase, s.index)
        if s.pulsed:
            ck.require(bool(s.fired), f"{tag} holds a pulse, none detected",
                       key)
            ck.require(bool(s.trials and s.trials["files_ok"]),
                       f"{tag}: candidate files missing or short", key)
        else:
            ck.require(not s.fired, f"{tag} holds no pulse but fired "
                       f"({s.detections} detections)", key)
    compared = 0
    streams = run.params["streams"]
    for s in done:
        if s.series is None:
            continue
        series = np.asarray(s.series).reshape(streams, -1)
        peaks = np.asarray(s.snr_peaks).reshape(streams, -1)
        for st in range(streams):
            name = f"{chain.stream_tag(s.file_seg, st)}.t0"
            where = f"{s.phase}.{s.index}.file_seg{s.file_seg}" \
                + (f".p{st}" if st else "")
            ck.number(f"series_gap.{where}",
                      check.series_gap(series[st], ref[f"{name}.series"]),
                      "series_gap")
            if not s.pulsed:
                continue
            ck.number(f"snr_gap.{where}",
                      check.relative_gap(peaks[st],
                                         ref[f"{name}.snr_peaks"]),
                      "snr_gap")
            got_bin = int(np.argmax(series[st]))
            ck.number(f"bin_gap.{where}",
                      abs(got_bin - int(ref[f"{name}.peak_bins"][0])),
                      "bin_gap")
            ck.require(abs(got_bin - lay.expected_bin(s.file_seg)) <= 8,
                       f"{where}: peak at time bin {got_bin}, injected at "
                       f"{lay.expected_bin(s.file_seg)}")
        if s.phase == "window":
            compared += 1
        s.series = None
    ck.require(compared > 0, "no sampled segment of the window was "
               "compared with the reference")
    say(f"compared with the reference: {compared} window segment(s) of "
        f"file segments {run.sampled}, and the warm-up pulse")


DRIVERS = {"served": run}
