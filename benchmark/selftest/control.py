#!/usr/bin/env python3
"""The controls at a cell's own size: the float64 reference put in the
program's place and computed in the nearest lower precision, compared
with the float64 reference by the benchmark's own numbers.

    python benchmark/selftest/control.py --workload <cell> --seeds 3

For each seed it makes the cell's replay file (on the device, as a run
does), takes the warm-up pulse's segment and the window segment a run
would sample, and prints, for each control, the number the comparison
would read.  The limit of each number (the workload file's
``check.limits``) has to lie below the smallest of these and above the
largest that sound runs of the program print.  Each control's numbers
also go through the run's own ``Checks`` with the cell's limits, and the
last line printed is one JSON object with, per control, ``correct``
(which has to be false) and the ``checks`` a run's result line would
carry: the failing number beside its limit.  It drives no program and
measures nothing; run it on the chip so that the data are the run's.  Of
a file with several streams it reads stream 0.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="j1644_2p27.replay_quiet")
    ap.add_argument("--root", default=os.path.dirname(HERE))
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=9001)
    args = ap.parse_args(argv)

    from benchmark import check, gen, spec as spec_mod
    from benchmark.drivers.dmgrid import near_trials
    from benchmark.reference import chain

    root = os.path.abspath(args.root)
    sp = spec_mod.Spec(root, args.workload)
    p = chain.params_from_config(sp.config["options"])
    workers = chain.default_workers()
    work = os.path.join(spec_mod.CHECKOUT, ".bench_work", "control")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    path = os.path.join(work, "baseband.bin")
    dms = p["dm_list"] or [p["dm"]]
    near = near_trials(dms, float(sp.workload["pulses"]["dm"]))
    outer = [j for j in range(len(dms)) if j not in near]
    readings = {}
    limits = sp.workload["check"]["limits"]
    verdicts = {low: check.Checks(limits) for low in ("chirp_f32", "bf16")}
    # a run compares the boxcars' S/N in the served cell (pulsed segments
    # only) and the trials' in the grid
    snr_row = "snr_gap_trials" if outer else "snr_gap_boxcars"
    try:
        for seed in range(args.first_seed, args.first_seed + args.seeds):
            lay = gen.Layout(p, sp.workload, seed)
            gen.write_file(path, p, lay, seed)
            sampled = lay.draw_sample(sp.workload["check"]["sample"], seed)
            for k in [0] + sampled[:1]:
                raw = np.fromfile(path, dtype=np.uint8,
                                  count=lay.segment_bytes,
                                  offset=k * lay.stride_bytes)
                raw = chain.deinterleave(raw, p)[0]
                spec = chain.cleaned_spectrum(raw, p, workers)
                sound = [chain.trial(spec, p, d, workers) for d in dms]
                for low in ("chirp_f32", "bf16"):
                    s = chain.to_bf16(spec) if low == "bf16" else spec
                    got = [chain.trial(s, p, d, workers, low) for d in dms]
                    row = {
                        "series_gap": max(check.series_gap(
                            g["time_series"], w["time_series"])
                            for g, w in zip(got, sound)),
                        "snr_gap_boxcars": max(check.relative_gap(
                            g["snr_peaks"], w["snr_peaks"])
                            for g, w in zip(got, sound)),
                        "snr_gap_trials": check.relative_gap(
                            [max(got[j]["snr_peaks"]) for j in near],
                            [max(sound[j]["snr_peaks"]) for j in near]),
                        "bin_gap": max(abs(g["peak_bins"][0]
                                           - w["peak_bins"][0])
                                       for g, w in zip(got, sound)),
                    }
                    if outer:
                        row["snr_gap_outer"] = check.relative_gap(
                            [max(got[j]["snr_peaks"]) for j in outer],
                            [max(sound[j]["snr_peaks"]) for j in outer])
                        row["sound_peak_snr"] = [
                            round(max(w["snr_peaks"]), 3) for w in sound]
                    kind = "pulse" if lay.pulsed[k] else "quiet"
                    print(f"[control] seed {seed} file_seg {k} ({kind}) "
                          f"{low}: {json.dumps(row)}", flush=True)
                    row_numbers = {n: v for n, v in row.items()
                                   if n != "sound_peak_snr"}
                    for name, v in row_numbers.items():
                        readings.setdefault((low, kind, name), []).append(v)
                    where = f"{low}.seed{seed}.file_seg{k}"
                    for key in limits:
                        name = snr_row if key == "snr_gap" else key
                        if key == "series_gap" or kind == "pulse":
                            verdicts[low].number(f"{key}.{where}",
                                                 row[name], key)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    for (low, kind, name), vals in sorted(readings.items()):
        print(f"[control] smallest {name} of {low} on {kind} segments "
              f"over {len(vals)} reading(s): {min(vals)!r}")
    print(json.dumps({"control": {
        low: {"correct": ck.ok, "checks": ck.summary()}
        for low, ck in verdicts.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
