"""``dmgrid``: the DM-trial grid across the chips of one host, built the
way ``srtb_tpu/tools/main.py``'s ``--dm_list`` branch builds it —
``Config.from_args`` then ``DMSearchPipeline(cfg, source=...)``.

Its loop is synchronous and has no sinks: a segment is complete when its
record is in ``<prefix>dm_trials.jsonl`` and the loop asks the source for
the next one, which is where the source stamps it.  What the timed path
hands out per segment is that record (per-trial peak S/N and counts; the
series and the peak bin never leave the chips), so that is what is
compared: EVERY trial's peak S/N with the float64 reference, so that each
chip's shard of the grid is held to it.  Two numbers, because the trials
answer differently: ``snr_gap`` over the matched trial and its two
neighbours (the pulse stands in them; the lower-precision controls fail
it), ``snr_gap_outer`` over the others (there the peak is the smeared
pulse or a noise peak, which lower precision hardly moves; it is held
against a shard that comes back zeroed, garbled or in another order).
"""

from __future__ import annotations

import json

import numpy as np

from benchmark import check
from benchmark.harness import say


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
    params = dict(wl["source"], complete_on_next=True)
    source = sources[wl["source"]["kind"]](cfg, lay, run.rec, params)
    search = DMSearchPipeline(cfg, source=source)
    say(f"mesh: {dict(search.mesh.shape)}; {len(dm_list)} trials "
        f"{dm_list}, all compared; the pulse stands in trials {near}")
    try:
        source.begin("warmup")
        search.run()
        source.end_phase()
        say("warm-up done")
        ref = run.join_reference()
        run.open_window(source)
        search.run()
        source.end_phase()
        run.close_window()
    finally:
        source.end_phase()
    with open(search.trials_path) as f:
        records = [json.loads(ln) for ln in f]
    if len(records) != len(run.rec.segs):
        raise RuntimeError(f"{len(records)} trial records for "
                           f"{len(run.rec.segs)} segments")
    for s, r in zip(run.rec.segs, records):
        s.trials = r
    judge(run, ref, dm_list, near, pulse_dm)


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
