#!/usr/bin/env python3
"""On-chip smoke: the served J1644-4559 path through the normal entry
points at a real size, on one chip, in one process.

    python chip_smoke.py                       # one TPU chip, 2^27 segments
    python chip_smoke.py --log2n 30 --channels "2**15" --segments 2
    python chip_smoke.py --chips 4             # only the DM-trial grid

What it runs (every phase in THIS process — a chip belongs to one):

1. ``default``: a seeded synthetic J1644-4559 baseband (2-bit, 128 MSa/s,
   1437 MHz / -64 MHz, DM -478.80, two dispersed pulses at known
   positions) through ``srtb_tpu.tools.main.main(argv)`` -> ``Pipeline``
   with file input, writers on, the default plan, the overlap-save ring,
   the telemetry journal, and the self-healing ladder OFF — a compile or
   device fault ends the run instead of demoting onto a smaller plan.
2. ``warm``: the same config again on the file's last two segments; the
   persistent compilation cache must serve every program (0 misses).
3. ``pallas``: the same two segments with ``--use_pallas 1
   --use_pallas_sk 1`` (Mosaic kernels, never interpret mode on a chip),
   detections compared with phase 1's.

It then checks what came out — one journal span per segment, both pulses
detected where they were injected, the other segments quiet, 0 plan
demotions, 0 device reinits — and fails otherwise.

The LAST line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
everything else is printed on earlier lines (the repo's logger writes to
stderr).  Without a TPU it exits non-zero and prints no result, unless
the test-only ``--allow-cpu`` is given (then ``platform`` says ``cpu``).
Any rate printed here is a smoke reading, not a benchmark.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time
import traceback

# J1644-4559 observation parameters
# (examples/srtb_config_1644-4559.cfg, examples/j1644_synthetic.sh)
FREQ_LOW = 1405.0 + 32.0
BANDWIDTH = -64.0
SAMPLE_RATE = 128e6
DM = -478.80
NBITS = 2
SNR_THRESHOLD = 8.0
PULSE_AMP = 40.0
PULSE_WIDTH = 32


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--log2n", type=int, default=27,
                   help="log2 of baseband_input_count (default 27: one "
                        "second of sky per segment)")
    p.add_argument("--channels", default="2**11",
                   help="spectrum_channel_count (expression)")
    p.add_argument("--segments", type=int, default=4,
                   help="full segments in the synthetic file (>= 2)")
    p.add_argument("--seed", type=int, default=3)
    p.add_argument("--pulse-amp", type=float, default=PULSE_AMP,
                   help="injected pulse amplitude in noise sigmas "
                        "(lower it at test sizes, where 40 sigma swamps "
                        "the 2-bit digitizer)")
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4 = run ONLY the DM-trial grid across four "
                        "chips and its one-chip comparison")
    p.add_argument("--workdir", default="",
                   help="scratch directory (default: "
                        "<checkout>/.smoke_work, emptied first)")
    p.add_argument("--allow-cpu", action="store_true",
                   help="test-only: let the script run without a TPU")
    return p.parse_args(argv)


# ------------------------------------------------------------- the data


class Layout:
    """Where the segments and the injected pulses sit in the file."""

    def __init__(self, cfg, n_segments: int):
        from srtb_tpu.ops import dedisperse as dd

        self.n = cfg.baseband_input_count
        self.reserved = int(dd.nsamps_reserved(cfg))
        check(0 < self.reserved < self.n,
              f"no overlap-save tail at n={self.n} dm={cfg.dm} "
              f"(reserved={self.reserved}): the ring would not run")
        self.stride = self.n - self.reserved
        self.n_segments = n_segments
        self.total = self.reserved + n_segments * self.stride
        # synthesis unit: long enough to hold a whole dispersion sweep
        # (= reserved / 2 samples) well inside it, short enough for a
        # float64 FFT on the host
        self.unit = min(self.n // 2, 1 << 26)
        check(self.unit >= 2 * self.reserved,
              "dispersion sweep too long for the synthesis unit")
        sweep = self.reserved // 2
        # one pulse in every odd segment, at the centre of the synthesis
        # unit nearest the middle of that segment's un-overlapped span
        self.pulses = {}
        for k in range(1, n_segments, 2):
            mid = k * self.stride + self.stride // 2
            u = mid // self.unit
            g = u * self.unit + self.unit // 2
            check(k * self.stride + sweep <= g
                  < (k + 1) * self.stride - sweep,
                  f"pulse for segment {k} does not fit its unique span")
            self.pulses[k] = g

    def sample_bytes(self, samples: int) -> int:
        return samples * NBITS // 8


def synthesize(path: str, lay: Layout, cfg, seed: int,
               pulse_amp: float) -> float:
    """Write the seeded baseband: distinct Gaussian noise per unit, a
    dispersed pulse (io/synth.make_dispersed_baseband) in the units that
    hold one.  NumPy, in this process.  Returns seconds taken."""
    import numpy as np

    from srtb_tpu.io import synth

    t0 = time.perf_counter()
    n_units = -(-lay.total // lay.unit)
    pulse_units = {g // lay.unit: g % lay.unit
                   for g in lay.pulses.values()}
    written = 0
    with open(path, "wb") as f:
        for u in range(n_units):
            if u in pulse_units:
                data = synth.make_dispersed_baseband(
                    lay.unit, cfg.baseband_freq_low,
                    cfg.baseband_bandwidth, cfg.dm, [pulse_units[u]],
                    nbits=NBITS, pulse_amp=pulse_amp,
                    pulse_width=PULSE_WIDTH, seed=seed + 1000 * (u + 1))
            else:
                rng = np.random.default_rng(seed + 1000 * (u + 1))
                data = synth.quantize(
                    rng.standard_normal(lay.unit, dtype=np.float32),
                    NBITS)
            want = lay.sample_bytes(lay.total) - written
            data = data[:want]
            f.write(data.tobytes())
            written += data.nbytes
    return time.perf_counter() - t0


# ------------------------------------------------------------ one phase


def observation_argv(n: int, channels: int, dm: float) -> list:
    """The J1644-4559 observation as tools.main options."""
    return [
        "--baseband_input_count", str(n),
        "--baseband_input_bits", str(NBITS),
        "--baseband_format_type", "simple",
        "--baseband_freq_low", repr(FREQ_LOW),
        "--baseband_bandwidth", f" {BANDWIDTH!r}",
        "--baseband_sample_rate", repr(SAMPLE_RATE),
        "--dm", f" {dm!r}",
        "--spectrum_channel_count", str(channels),
        "--signal_detect_signal_noise_threshold", repr(SNR_THRESHOLD),
        "--mitigate_rfi_spectral_kurtosis_threshold", "1.05",
    ]


def served_argv(n: int, channels: int, dm: float) -> list:
    """The served single-chip path: overlap-save ring on, reproducible
    file names, and a compile or device fault ends the run — no
    demotion onto a smaller plan, no reinit."""
    return observation_argv(n, channels, dm) + [
        "--baseband_reserve_sample", "1",
        "--deterministic_timestamps", "1",
        "--plan_ladder", "off",
        "--device_reinit_max", "0",
    ]


class CompileEvents:
    """What JAX itself reports (jax.monitoring): persistent-cache hits
    and misses, and the seconds of every backend compile."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        self.backend_compile_s = []
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s.append(round(duration, 2))

    def report(self, name: str) -> tuple:
        """Print and reset what was counted since the last call;
        returns (hits, misses)."""
        hits, misses = self.hits, self.misses
        say(f"{name}: persistent cache hits={hits} misses={misses}; "
            "backend compile (or cache load) seconds, programs over "
            f"0.5 s: {[s for s in self.backend_compile_s if s > 0.5]}")
        self.hits = self.misses = 0
        self.backend_compile_s = []
        return hits, misses


def run_phase(name: str, argv: list, workdir: str, lay: Layout,
              first_segment: int, channels: int) -> dict:
    """One ``tools.main.main(argv)`` run and its checks.  Returns the
    per-segment detections for cross-phase comparison."""
    import numpy as np

    from srtb_tpu.io.file_input import DETERMINISTIC_EPOCH_NS
    from srtb_tpu.tools.main import main as srtb_main
    from srtb_tpu.utils.metrics import metrics

    out_dir = os.path.join(workdir, name)
    os.makedirs(out_dir)
    prefix = os.path.join(out_dir, "out_")
    journal = os.path.join(out_dir, "journal.jsonl")
    offset = lay.sample_bytes(first_segment * lay.stride)
    metrics.reset()
    t0 = time.perf_counter()
    rc = srtb_main(argv + [
        "--input_file_offset_bytes", str(offset),
        "--baseband_output_file_prefix", prefix,
        "--telemetry_journal_path", journal,
    ])
    wall = time.perf_counter() - t0
    check(rc == 0, f"{name}: tools.main returned {rc}")

    spans = [json.loads(ln) for ln in open(journal)]
    spans = [s for s in spans if s.get("type") == "segment_span"]
    n_full = lay.n_segments - first_segment
    check(len(spans) >= n_full,
          f"{name}: {len(spans)} journal spans for {n_full} segments")
    check([s["segment"] for s in spans] == list(range(len(spans))),
          f"{name}: journal spans are not one per segment, in order")
    check(int(metrics.get("segments")) == len(spans),
          f"{name}: segments counter {metrics.get('segments')} != "
          f"{len(spans)} journal spans")
    for key in ("plan_demotions", "device_reinits"):
        check(int(metrics.get(key)) == 0 and spans[-1][key] == 0,
              f"{name}: {key} = {metrics.get(key)} (must be 0)")

    detections = {}
    for i, span in enumerate(spans):
        k = first_segment + i
        padded = k >= lay.n_segments  # the reader's zero-padded tail
        base = prefix + str(DETERMINISTIC_EPOCH_NS
                            + lay.sample_bytes(k * lay.stride))
        tims = sorted(glob.glob(base + ".*.tim"),
                      key=lambda p: int(p.split(".")[-2]))
        rec = {"detections": span["detections"], "snr": None,
               "bin": None, "padded": padded}
        if tims:
            series = np.fromfile(tims[0], dtype="<f4")
            rec["snr"] = float(series.max()
                               / np.sqrt(np.mean(series * series)))
            rec["bin"] = int(series.argmax())
            rec["boxcar"] = int(tims[0].split(".")[-2])
        detections[k] = rec
        if padded:
            continue
        if k in lay.pulses:
            want_bin = (lay.pulses[k] - k * lay.stride) // (2 * channels)
            check(span["detections"] > 0 and span["dump"],
                  f"{name}: segment {k} holds a pulse, none detected")
            check(os.path.exists(base + ".bin") and tims,
                  f"{name}: segment {k}: candidate files missing")
            check(rec["snr"] > SNR_THRESHOLD,
                  f"{name}: segment {k}: SNR {rec['snr']} <= threshold")
            check(abs(rec["bin"] - want_bin) <= rec["boxcar"] + 4,
                  f"{name}: segment {k}: peak at time bin {rec['bin']}, "
                  f"injected at {want_bin}")
        else:
            check(span["detections"] == 0 and not span["dump"],
                  f"{name}: segment {k} holds no pulse but "
                  f"{span['detections']} detections")
            check(not os.path.exists(base + ".bin"),
                  f"{name}: segment {k}: candidate written for a quiet "
                  "segment")

    samples = sum(s["samples"] for s in spans)
    say(f"{name}: {len(spans)} segments of 2^{lay.n.bit_length() - 1} "
        f"samples through tools.main -> Pipeline, plan "
        f"{spans[-1].get('active_plan')}, wall {wall:.2f} s "
        f"({samples / wall / 1e6:.1f} Msamples/s incl. compile and "
        "file I/O — smoke, not a benchmark)")
    say(f"{name}: first dispatches (trace + compile or cache load + "
        f"first run): {spans[-1]['compile_ms'] / 1e3:.2f} s over "
        f"{spans[-1]['plan_compiles']} programs")
    dev_ms = [s["device_ms"] for s in spans if "device_ms" in s]
    if dev_ms:
        say(f"{name}: device_ms per segment {dev_ms}")
    for k, rec in sorted(detections.items()):
        tag = ("zero-padded tail, not gated" if rec["padded"]
               else "pulse" if k in lay.pulses else "quiet")
        say(f"{name}: segment {k} [{tag}]: detections="
            f"{rec['detections']} snr={rec['snr']} bin={rec['bin']}")
    say(f"{name}: plan_demotions=0 device_reinits=0")
    return detections


# ---------------------------------------------------------- single chip


def run_single_chip(args, workdir: str) -> None:
    import jax

    from srtb_tpu.config import Config
    from srtb_tpu.io import native_writer
    from srtb_tpu.pipeline import registry
    from srtb_tpu.utils import compile_cache
    from srtb_tpu.utils.expression import parse_expression
    from srtb_tpu.utils.platform import on_accelerator

    n = 1 << args.log2n
    # below the deployment size the DM shrinks with the segment so the
    # overlap-save tail keeps its share of it (test sizes only)
    dm = DM * min(1.0, n / (1 << 27))
    channels = int(parse_expression(args.channels))
    argv = served_argv(n, channels, dm)
    cfg = Config.from_args(argv)
    check(args.segments >= 2, "--segments must be >= 2")
    lay = Layout(cfg, args.segments)
    say(f"config: J1644-4559, n=2^{args.log2n}, channels={channels}, "
        f"dm={dm}, reserved={lay.reserved} samples, stride={lay.stride}, "
        f"{args.segments} full segments, pulses at samples "
        f"{sorted(lay.pulses.values())} (segments {sorted(lay.pulses)})")

    # candidate files go through the Python writer pool: the checked-in
    # libsrtb_writer.so is a binary this checkout's sources did not
    # build, so the smoke does not depend on it
    native_writer._NATIVE = None
    say("writers: Python writer pool (native libsrtb_writer.so not used)")

    path = os.path.join(workdir, "baseband.bin")
    dt = synthesize(path, lay, cfg, args.seed, args.pulse_amp)
    say(f"synthesized {os.path.getsize(path)} bytes in {dt:.1f} s "
        f"(seed {args.seed}; distinct noise per 2^"
        f"{lay.unit.bit_length() - 1}-sample unit, nothing tiled)")
    argv += ["--input_file_path", path]

    cache_dir = compile_cache.enable_compile_cache()
    say(f"compile cache: {cache_dir} (JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', '<unset>')})")
    events = CompileEvents()

    first = run_phase("default", argv, workdir, lay, 0, channels)
    events.report("default")

    # a second Pipeline on the same config, the file's last two segments
    tail = max(0, lay.n_segments - 2)
    warm = run_phase("warm", argv, workdir, lay, tail, channels)
    hits, misses = events.report("warm")
    if cache_dir is not None:
        check(misses == 0 and hits > 0,
              f"warm: the second Pipeline compiled {misses} program(s) "
              "instead of loading them from the cache")
    compare(first, warm, "warm", exact=True)

    pallas_argv = argv + ["--use_pallas", "1", "--use_pallas_sk", "1"]
    pallas = run_phase("pallas", pallas_argv, workdir, lay, tail,
                       channels)
    # the same plan, built the way Pipeline builds it: never interpret
    # mode on a chip, and the lowered programs carry the Mosaic kernels
    proc = registry.build_processor(
        Config.from_args(pallas_argv), donate_input=on_accelerator())
    check(proc._pallas_interpret is (not on_accelerator())
          and (on_accelerator() or args.allow_cpu),
          f"pallas: interpret mode = {proc._pallas_interpret} with "
          f"backend {jax.default_backend()}")
    if on_accelerator():
        for prog, fn, avals, _donated in proc.lowerables():
            check("tpu_custom_call" in fn.lower(*avals).as_text(),
                  f"pallas: program {prog} holds no Mosaic kernel")
    say(f"pallas: Pallas interpret mode = {proc._pallas_interpret}; "
        "Mosaic custom calls in "
        + (", ".join(p[0] for p in proc.lowerables())
           if on_accelerator() else "nothing (no chip)"))
    del proc
    events.report("pallas")
    compare(first, pallas, "pallas", exact=False)

    stats = jax.devices()[0].memory_stats() or {}
    say(f"device memory: peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use', 'not reported')} "
        f"bytes_limit={stats.get('bytes_limit', 'not reported')}")


def compare(ref: dict, got: dict, name: str, exact: bool) -> None:
    """Per-segment detections of a later phase against phase one's."""
    for k, rec in sorted(got.items()):
        if rec["padded"] or k not in ref:
            continue
        want = ref[k]
        check((rec["detections"] > 0) == (want["detections"] > 0),
              f"{name}: segment {k}: detected={rec['detections']} but "
              f"default phase had {want['detections']}")
        if want["snr"] is None:
            continue
        tol = 1e-6 if exact else 0.05
        check(abs(rec["snr"] - want["snr"]) <= tol * want["snr"]
              and abs(rec["bin"] - want["bin"]) <= (0 if exact else 1),
              f"{name}: segment {k}: snr/bin {rec['snr']}/{rec['bin']} "
              f"vs default {want['snr']}/{want['bin']}")
        say(f"{name}: segment {k} agrees with the default phase "
            f"(snr {rec['snr']:.3f} vs {want['snr']:.3f}, bin "
            f"{rec['bin']} vs {want['bin']})")


# ----------------------------------------------------------- four chips


def run_four_chips(args, workdir: str) -> None:
    """The DM-trial grid (--dm_list, DMSearchPipeline) on a 4-device
    ("dm", "seq") mesh, against the same trials one at a time on
    device 0."""
    import jax
    import numpy as np

    from srtb_tpu.config import Config
    from srtb_tpu.io import synth
    from srtb_tpu.parallel import mesh as M
    from srtb_tpu.pipeline.runtime import DMSearchPipeline
    from srtb_tpu.tools.main import main as srtb_main
    from srtb_tpu.utils.expression import parse_expression

    log2n = min(args.log2n, 24)
    n = 1 << log2n
    channels = int(parse_expression(args.channels))
    dm0 = DM * min(1.0, n / (1 << 24))
    trials = [dm0 * (1.0 + 0.02 * (i - 3)) for i in range(8)]
    say(f"four chips: DM-trial grid, n=2^{log2n}, channels={channels}, "
        f"{len(trials)} trials {trials} (injected at {dm0})")
    path = os.path.join(workdir, "baseband_dm.bin")
    synth.make_dispersed_baseband(
        n, FREQ_LOW, BANDWIDTH, dm0, [n // 2], nbits=NBITS,
        pulse_amp=args.pulse_amp, pulse_width=PULSE_WIDTH,
        seed=args.seed).tofile(path)
    argv = observation_argv(n, channels, dm0) + [
        "--input_file_path", path,
        "--baseband_reserve_sample", "0",
        "--use_emulated_fp64", "1",
    ]
    grid_dir = os.path.join(workdir, "grid")
    os.makedirs(grid_dir)
    prefix = os.path.join(grid_dir, "out_")
    t0 = time.perf_counter()
    rc = srtb_main(argv + [
        "--baseband_output_file_prefix", prefix,
        "--dm_list", " " + ",".join(repr(d) for d in trials)])
    check(rc == 0, f"four chips: tools.main returned {rc}")
    say(f"four chips: grid run wall {time.perf_counter() - t0:.2f} s "
        "(incl. compile — smoke, not a benchmark)")
    grid = [json.loads(ln) for ln in open(prefix + "dm_trials.jsonl")]
    check(len(grid) == 1, f"four chips: {len(grid)} trial records")
    grid = grid[0]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    say(f"four chips: peak_bytes_in_use by device "
        f"{[p if p is not None else 'not reported' for p in peaks]}")
    if all(p is not None for p in peaks):
        check(min(peaks) > 0.5 * max(peaks),
              f"four chips: the work is not spread over the devices: "
              f"peak bytes {peaks}")

    # the comparison: the same trials, one at a time, on device 0 alone
    one = M.make_mesh(n_dm=1, n_seq=1, devices=jax.devices()[:1])
    solo_peaks = []
    for i, d in enumerate(trials):
        cfg = Config.from_args(argv + [
            "--baseband_output_file_prefix",
            os.path.join(workdir, f"solo{i}_"),
            "--dm_list", f" {d!r}"])
        DMSearchPipeline(cfg, mesh=one).run()
        rec = json.loads(open(
            cfg.baseband_output_file_prefix + "dm_trials.jsonl"
        ).readline())
        solo_peaks.append(rec["peak_snr"][0])
    best_solo = trials[int(np.argmax(solo_peaks))]
    say(f"four chips: grid peak_snr {grid['peak_snr']}")
    say(f"four chips: solo peak_snr {solo_peaks}")
    check(grid["best_dm"] == best_solo == dm0,
          f"four chips: best dm grid={grid['best_dm']} solo={best_solo} "
          f"injected={dm0}")
    check(np.allclose(grid["peak_snr"], solo_peaks, rtol=1e-3),
          "four chips: per-trial peak SNR differs between the grid and "
          "the one-chip runs")
    check(grid["best_snr"] > SNR_THRESHOLD, "four chips: pulse not found")
    say(f"four chips: best dm {grid['best_dm']} (snr "
        f"{grid['best_snr']:.2f}) on the mesh and one trial at a time")


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        import jax

        import srtb_tpu  # noqa: F401
    except ImportError as e:
        print(f"[chip_smoke] cannot import the program: {e}",
              file=sys.stderr)
        return 2
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"[chip_smoke] JAX found no device: {e}", file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not args.allow_cpu:
        print(f"[chip_smoke] no TPU: JAX reports {device}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} but JAX reports "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    say(f"device: {device}, jax {jax.__version__}")

    here = os.path.dirname(os.path.abspath(__file__))
    workdir = args.workdir or os.path.join(here, ".smoke_work")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    ok = False
    try:
        if args.chips == 4:
            run_four_chips(args, workdir)
        else:
            run_single_chip(args, workdir)
        ok = True
    except Exception:
        traceback.print_exc()
        say("FAILED (traceback on stderr)")
    finally:
        # a chip run keeps its journals and trial records (small) where
        # the chip tool brings them back; the baseband and candidates
        # are large
        keep = os.path.join(here, "chiprun_out", "smoke")
        kept = glob.glob(os.path.join(workdir, "**", "*.jsonl"),
                         recursive=True) \
            if device["platform"] == "tpu" else []
        for path in kept:
            dst = os.path.join(keep, os.path.relpath(path, workdir))
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            shutil.copy(path, dst)
        shutil.rmtree(workdir, ignore_errors=True)
    sys.stderr.flush()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
