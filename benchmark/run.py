#!/usr/bin/env python3
"""One run of one cell of the benchmark, in one process that holds the
cell's chips:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
metric is a file found by the name in ``BENCHMARK.json`` (see README.md);
this file only orders the steps: find the chip (or fail), make the data
from the seed, start the float64 reference in a child, build the program
through its own entry points, warm up, measure for ``--seconds``, compare,
and print one JSON object as the LAST line of standard output (the
program's logger writes to stderr).

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits non-zero.  ``--allow-cpu`` is for rehearsals and tests
only: the result line then says ``"platform": "cpu"`` and is not a
measurement.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--allow-cpu", action="store_true",
                   help="rehearsals and tests only: run without a TPU")
    p.add_argument("--root", default=HERE,
                   help="directory holding workloads/, end_to_end/ and "
                        "layer_metrics/ (selftest/ has a tiny copy)")
    p.add_argument("--keep-work", action="store_true",
                   help="leave the work directory for inspection")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from benchmark import spec as spec_mod
    from benchmark.harness import NoAccelerator, Run, say

    root = os.path.abspath(args.root)
    spec = spec_mod.Spec(root, args.workload)
    try:
        import srtb_tpu  # noqa: F401 - the system under test
    except ImportError as e:
        print(f"[bench] cannot import the program: {e}", file=sys.stderr)
        return 2
    run = Run(spec, args, T_START)
    try:
        run.check_devices()
    except NoAccelerator as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    sources = spec_mod.load_registry("sources", "KINDS")
    drivers = spec_mod.load_registry("drivers", "DRIVERS")
    reducers = spec_mod.load_registry("reducers", "REDUCERS")
    out = None
    try:
        run.enable_cache()
        run.make_workdir()
        run.make_data()
        drivers[spec.workload["driver"]](run, sources)
        run.read_trace()
        run.device_memory()
        out = run.result(reducers)
    except Exception:
        traceback.print_exc()
        say("FAILED (traceback on stderr); no result")
    finally:
        run.cleanup()
    if out is None:
        sys.stderr.flush()
        return 1
    # every number compared beside its limit: the last lines of stderr
    # (after the program's logger) and, under ``checks``, of the result
    for line in run.checks.lines():
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
