#!/usr/bin/env python3
"""``control.py`` on each stream of a several-stream cell.

    python benchmark/selftest/control_streams.py \
        --workload j1644_2pol_2p27.replay_quiet --seeds 2

``control.py`` reads stream 0 of what ``chain.deinterleave`` returns (its
docstring says so), and a run compares every stream.  A limit of a
several-stream cell has to lie under the smallest control reading of
EACH stream, so this file runs ``control.main`` once per stream of the
cell's format, with the wanted stream handed over first, on the same
seeds, and prints what it printed under ``[stream <s>]``.  The last line
is one JSON object: per stream, per control, ``correct`` (which has to be
false on every stream) and the ``checks`` beside the cell's limits.  The
exit code is 0 when every control fails on every stream, 1 otherwise.
Like ``control.py`` it drives no program; run it on the chip so that the
data are the run's.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), HERE]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="j1644_2pol_2p27.replay_quiet")
    ap.add_argument("--root", default=os.path.dirname(HERE))
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=9001)
    args = ap.parse_args(argv)

    import control
    from benchmark import spec as spec_mod
    from benchmark.reference import chain

    sp = spec_mod.Spec(os.path.abspath(args.root), args.workload)
    streams = chain.params_from_config(sp.config["options"])["streams"]
    deinterleave = chain.deinterleave
    verdicts = {}
    for s in range(streams):
        chain.deinterleave = \
            lambda raw, p, s=s: [deinterleave(raw, p)[s]]
        said = io.StringIO()
        try:
            with contextlib.redirect_stdout(said):
                control.main(["--workload", args.workload,
                              "--root", args.root,
                              "--seeds", str(args.seeds),
                              "--first-seed", str(args.first_seed)])
        finally:
            chain.deinterleave = deinterleave
        lines = said.getvalue().splitlines()
        for line in lines[:-1]:
            print(f"[stream {s}] {line}", flush=True)
        verdicts[f"stream{s}"] = json.loads(lines[-1])["control"]
    print(json.dumps({"limits": sp.workload["check"]["limits"],
                      "control": verdicts}), flush=True)
    failed = all(not v["correct"] for per in verdicts.values()
                 for v in per.values())
    return 0 if failed else 1


if __name__ == "__main__":
    sys.exit(main())
