"""SIGKILL crash soak: durable exactly-once outputs under process death.

``tools/chaos_soak.py`` soaks *in-process* fault recovery; this harness
soaks the one thing no in-process mechanism can handle — the process
dying outright.  It runs the file-mode pipeline as a SUBPROCESS and
``SIGKILL``s it at seeded-random points, steered deterministically into
the nastiest crash windows:

- ``ckpt_stall@i``  — ``Config.fault_plan`` ``checkpoint:stall`` parks
  the child between segment *i*'s sink commits and its checkpoint
  update (the classic duplicate-on-resume window); the parent kills it
  mid-stall;
- ``sink_stall@i``  — ``sink_write:stall`` parks it after the fetch,
  before any artifact write (the clean-loss window);
- ``rename@N``      — the child arms ``io/writers._PRE_RENAME_HOOK``
  to park the *N*-th artifact write between its temp write and the
  atomic rename (orphan temp + uncommitted intent); the parent kills
  it mid-rename.

After each kill the child is simply restarted: ``Pipeline.__init__``
recovers the run manifest (io/manifest.py), rolls back uncommitted
artifacts, and the manifest done-set makes replayed sink pushes
idempotent.  When a child finally runs to completion the gate asserts:

- ``fsck`` (tools/fsck.py) is CLEAN — WAL CRCs, artifact
  existence/size/content-CRC, checkpoint agreement;
- the run directory's final output set (paths + bytes, SHA-256) is
  BIT-IDENTICAL to an uninterrupted golden run — zero duplicates,
  zero loss (file mode never sheds, so loss beyond accounted
  ``segments_dropped`` = any loss at all would break the equality);
- every planned SIGKILL actually landed, and no ``.srtb_tmp`` orphans
  survive.

File-mode artifact names embed the segment timestamp; subprocess runs
stamp timestamps deterministically from the stream offset
(:class:`DeterministicTimestampReader`) so names are reproducible
across golden/soak runs AND across resumes — which is also what makes
the paths+bytes equality an honest exactly-once check.

Usage::

    python -m srtb_tpu.tools.crash_soak [--seed N] [--segments N]
        [--kills N] [--log2n N] [--kill-plan "ckpt_stall@1,rename@2"]
        [--writer-threads N]

Exit 0 on a passing soak, 1 on any gate failure.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import subprocess
import sys
import tempfile
import threading
import time

from srtb_tpu.tools import SOAK_DM

STALL_S = 30.0          # long enough that the parent's kill always lands
CHILD_TIMEOUT_S = 300.0
_FIRING_MARK = "[faults] firing"
_RENAME_MARK = "SOAK_RENAME_STALL"
_STATS_MARK = "SOAK_STATS "
_RECOVERY_MARK = "SOAK_RECOVERY "


class SoakFailure(AssertionError):
    """One broken exactly-once invariant (the gate)."""


# ----------------------------------------------------------------
# child side
# ----------------------------------------------------------------

def make_resumable_source(cfg):
    """The file source a resumed child needs: checkpoint-aware start
    offset (mirroring Pipeline's own source construction) plus
    offset-derived deterministic timestamps — the first-class reader
    in io/file_input.py (``DeterministicTimestampReader``, promoted
    out of this tool so the soaks and the archive replay engine share
    one implementation)."""
    from srtb_tpu.io.file_input import DeterministicTimestampReader

    start = None
    if cfg.checkpoint_path and (
            os.path.exists(cfg.checkpoint_path)
            or os.path.exists(cfg.checkpoint_path + ".bak")):
        from srtb_tpu.pipeline.checkpoint import StreamCheckpoint
        ck = StreamCheckpoint(cfg.checkpoint_path)
        if ck.segments_done:
            start = ck.file_offset_bytes
    return DeterministicTimestampReader(cfg, start_offset_bytes=start)


def _child_main(cfg_path: str, stall_rename_at: int,
                stall_s: float) -> int:
    from srtb_tpu.config import Config
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.utils.metrics import metrics

    with open(cfg_path) as f:
        cfg = Config(**json.load(f))
    if cfg.writer_thread_count > 0:
        # pin the Python fallback pool: the native C++ pool renames in
        # C++ where the rename-stall hook cannot park, and its commit
        # granularity is the drain barrier — the py pool is the
        # deterministic per-artifact path this soak steers
        from srtb_tpu.io import native_writer
        native_writer._NATIVE = None
    if stall_rename_at > 0:
        from srtb_tpu.io import writers
        count = [0]

        def hook(path):
            count[0] += 1
            if count[0] == stall_rename_at:
                print(f"{_RENAME_MARK} {os.path.basename(path)}",
                      flush=True)
                time.sleep(stall_s)

        writers._PRE_RENAME_HOOK = hook
    src = make_resumable_source(cfg)
    with Pipeline(cfg, source=src) as pipe:
        # manifest recovery ran in the constructor; report it BEFORE
        # the run so the parent sees it even from a child it kills
        print(_RECOVERY_MARK + json.dumps({
            "recovered_segments":
                int(metrics.get("recovered_segments")),
            "rolled_back_intents":
                int(metrics.get("rolled_back_intents")),
        }), flush=True)
        stats = pipe.run()
    print(_STATS_MARK + json.dumps({
        "segments": stats.segments,
        "signals": stats.signals,
        "recovered_segments": int(metrics.get("recovered_segments")),
        "replayed_skips": int(metrics.get("replayed_skips")),
        "rolled_back_intents": int(metrics.get("rolled_back_intents")),
        "segments_dropped": int(metrics.get("segments_dropped")),
    }), flush=True)
    return 0


# ----------------------------------------------------------------
# parent side
# ----------------------------------------------------------------

def _child_cfg(tmp: str, run_dir: str, n: int, fault_plan: str = "",
               writer_threads: int = 0) -> dict:
    return dict(
        baseband_input_count=n, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6, dm=SOAK_DM,
        input_file_path=os.path.join(tmp, "bb.bin"),
        baseband_output_file_prefix=os.path.join(run_dir, "out_"),
        spectrum_channel_count=64,
        # zapping OFF in spirit: the soak needs every segment's pulse
        # to reach the detector so every segment writes artifacts
        mitigate_rfi_average_method_threshold=1000.0,
        mitigate_rfi_spectral_kurtosis_threshold=50.0,
        # deliberately below the noise floor: EVERY segment must write
        # artifacts (deterministically — same data, same decisions) so
        # each kill window has writes to land in and every segment
        # contributes to the exactly-once union
        signal_detect_signal_noise_threshold=1.5,
        signal_detect_max_boxcar_length=8,
        baseband_reserve_sample=True,
        writer_thread_count=writer_threads,
        fft_strategy="four_step",
        inflight_segments=2,
        checkpoint_path=os.path.join(run_dir, "ck.json"),
        run_manifest_path=os.path.join(run_dir, "manifest.jsonl"),
        fault_plan=fault_plan,
    )


def _run_child(run_dir: str, cfg: dict, kill_on: str | None = None,
               stall_rename_at: int = 0,
               timeout_s: float = CHILD_TIMEOUT_S) -> dict:
    """Spawn one pipeline child; with ``kill_on`` set, SIGKILL it as
    soon as that marker appears on its merged output.  Returns
    {rc, killed, stats, lines}."""
    cfg_path = os.path.join(run_dir, "cfg.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    cmd = [sys.executable, "-m", "srtb_tpu.tools.crash_soak",
           "--child", cfg_path]
    if stall_rename_at > 0:
        cmd += ["--stall-rename-at", str(stall_rename_at),
                "--stall-s", f"{STALL_S:g}"]
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            bufsize=1, env=env)
    # hard backstop so a wedged child can never hang the soak
    backstop = threading.Timer(timeout_s, proc.kill)
    backstop.daemon = True
    backstop.start()
    killed = False
    stats = None
    recovery = None
    lines: list[str] = []
    try:
        for line in proc.stdout:
            lines.append(line.rstrip("\n"))
            if line.startswith(_STATS_MARK):
                stats = json.loads(line[len(_STATS_MARK):])
            elif line.startswith(_RECOVERY_MARK):
                recovery = json.loads(line[len(_RECOVERY_MARK):])
            if kill_on is not None and not killed and kill_on in line:
                time.sleep(0.25)  # land the kill INSIDE the stall
                proc.kill()       # SIGKILL: no cleanup runs
                killed = True
        rc = proc.wait()
    finally:
        backstop.cancel()
        proc.stdout.close()
    replays = sum(1 for ln in lines if "skipping replay" in ln)
    return {"rc": rc, "killed": killed, "stats": stats,
            "recovery": recovery, "replayed_skips": replays,
            "lines": lines}


def _read_ck_done(run_dir: str) -> int:
    for name in ("ck.json", "ck.json.bak"):
        try:
            with open(os.path.join(run_dir, name)) as f:
                return int(json.load(f).get("segments_done", 0))
        except (OSError, ValueError):
            continue
    return 0


def snapshot_outputs(run_dir: str) -> dict:
    """relative name -> sha256 of every artifact in a run dir
    (manifest/checkpoint/config bookkeeping excluded)."""
    skip = {"manifest.jsonl", "ck.json", "ck.json.bak", "ck.json.tmp",
            "cfg.json"}
    out = {}
    for name in sorted(os.listdir(run_dir)):
        if name in skip:
            continue
        p = os.path.join(run_dir, name)
        if not os.path.isfile(p):
            continue
        h = hashlib.sha256()
        with open(p, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
        out[name] = h.hexdigest()
    return out


def parse_kill_plan(text: str) -> list[tuple[str, int]]:
    """"kind@arg,..." with kinds ckpt_stall|sink_stall (arg = per-run
    segment index) and rename (arg = Nth artifact write of the run)."""
    plan = []
    for entry in (e.strip() for e in text.split(",")):
        if not entry:
            continue
        try:
            kind, arg = entry.split("@", 1)
            kind = kind.strip()
            arg_i = int(arg)
        except ValueError as e:
            raise ValueError(f"kill-plan entry {entry!r}: expected "
                             "'kind@int'") from e
        if kind not in ("ckpt_stall", "sink_stall", "rename"):
            raise ValueError(f"kill-plan entry {entry!r}: unknown kind "
                             f"{kind!r}")
        plan.append((kind, arg_i))
    return plan


def generate_kill_plan(seed: int, kills: int) -> list[tuple[str, int]]:
    """Seeded random kill points.  The first two kills always cover
    the two named crash windows (mid-checkpoint-flush, mid-rename);
    the rest draw from all three kinds.  Stall indices are RELATIVE to
    each resumed run (re-clamped to the remaining segment count at
    launch, so every planned kill lands)."""
    rng = random.Random(seed)
    plan: list[tuple[str, int]] = []
    for i in range(kills):
        if i == 0:
            kind = "ckpt_stall"
        elif i == 1:
            kind = "rename"
        else:
            kind = rng.choice(("ckpt_stall", "sink_stall", "rename"))
        # small indices: each stall-steered kill advances the resumed
        # run by ~its index, and the soak must not outrun --segments
        # before every planned kill lands
        arg = (rng.randrange(1, 3) if kind == "rename"
               else rng.randrange(0, 3))
        plan.append((kind, arg))
    return plan


def run_soak(seed: int = 0, segments: int = 10, kills: int = 5,
             log2n: int = 13, kill_plan: str | None = None,
             writer_threads: int = 0,
             tmpdir: str | None = None) -> dict:
    """One full soak (golden run, kill loop, recovery to completion,
    gate).  Returns the report dict; raises :class:`SoakFailure` on
    any broken invariant."""
    from srtb_tpu.io.synth import make_dispersed_baseband
    from srtb_tpu.tools.fsck import fsck

    tmp = tmpdir or tempfile.mkdtemp(prefix="srtb_crash_")
    n = 1 << log2n
    # one pulse per overlap-save STRIDE window, so every segment the
    # reader emits is positive and writes artifacts — the rename
    # steering then always finds a write to park, and every segment
    # contributes to the exactly-once union
    from srtb_tpu.config import Config
    from srtb_tpu.ops import dedisperse as dd
    probe_cfg = Config(**_child_cfg(tmp, tmp, n))
    reserved = int(dd.nsamps_reserved(probe_cfg))
    stride = max(1, n - reserved)
    total_bytes = n * segments
    pulses = [reserved + i * stride + stride // 2
              for i in range((total_bytes - reserved) // stride + 1)
              if reserved + i * stride + stride // 2 < total_bytes]
    make_dispersed_baseband(
        total_bytes, 1405.0, 64.0, SOAK_DM,
        pulse_positions=pulses,
        pulse_amp=40.0, nbits=8, seed=seed,
    ).tofile(os.path.join(tmp, "bb.bin"))

    def check(cond, msg):
        if not cond:
            raise SoakFailure(msg)

    # golden: one uninterrupted run
    golden_dir = os.path.join(tmp, "golden")
    os.makedirs(golden_dir, exist_ok=True)
    res = _run_child(golden_dir,
                     _child_cfg(tmp, golden_dir, n,
                                writer_threads=writer_threads))
    check(res["rc"] == 0, f"golden run failed rc={res['rc']}:\n"
          + "\n".join(res["lines"][-20:]))
    golden_map = snapshot_outputs(golden_dir)
    total_segments = int(res["stats"]["segments"])
    check(res["stats"]["signals"] > 0 and golden_map,
          "golden run produced no artifacts — the soak would gate "
          "nothing (tune pulse_amp / detection thresholds)")

    # soak: kill, resume, repeat
    plan = (parse_kill_plan(kill_plan) if kill_plan
            else generate_kill_plan(seed, kills))
    soak_dir = os.path.join(tmp, "soak")
    os.makedirs(soak_dir, exist_ok=True)
    kills_done = 0
    resumes = 0
    all_res: list[dict] = []
    finished = False
    # whether any kill landed with a sealed-but-unchecked-pointed
    # group on disk — only then MUST a later resume replay-skip it
    # (a stalled NEGATIVE segment wrote nothing and owes no skip)
    expect_replay = False
    expect_rollback = False
    for kind, arg in plan:
        done = _read_ck_done(soak_dir)
        remaining = max(1, total_segments - done)
        if kind == "rename":
            cfg = _child_cfg(tmp, soak_dir, n,
                             writer_threads=writer_threads)
            res = _run_child(soak_dir, cfg, kill_on=_RENAME_MARK,
                             stall_rename_at=max(1, arg))
        else:
            site = ("checkpoint" if kind == "ckpt_stall"
                    else "sink_write")
            index = min(arg, remaining - 1)
            cfg = _child_cfg(
                tmp, soak_dir, n, writer_threads=writer_threads,
                fault_plan=f"{site}:stall={STALL_S:g}@{index}")
            res = _run_child(soak_dir, cfg, kill_on=_FIRING_MARK)
        resumes += 1
        all_res.append(res)
        if res["killed"]:
            kills_done += 1
            from srtb_tpu.io.manifest import (group_complete,
                                              scan_manifest)
            scan = scan_manifest(os.path.join(soak_dir,
                                              "manifest.jsonl"))
            floor = scan.checkpoint_floor()
            if any(k[1] >= floor and group_complete(g)
                   for k, g in scan.groups.items()):
                expect_replay = True
            if kind == "rename":
                expect_rollback = True
        elif res["rc"] == 0:
            # finished before the steering point was reached (e.g. a
            # rename index past the run's remaining writes)
            finished = True
            break
        else:
            raise SoakFailure(
                f"steered child died rc={res['rc']} without being "
                f"killed ({kind}@{arg}):\n"
                + "\n".join(res["lines"][-20:]))

    if not finished:
        # recovery to completion
        res = _run_child(soak_dir,
                         _child_cfg(tmp, soak_dir, n,
                                    writer_threads=writer_threads))
        check(res["rc"] == 0,
              f"final recovery run failed rc={res['rc']}:\n"
              + "\n".join(res["lines"][-20:]))
        all_res.append(res)
        resumes += 1

    check(kills_done == len(plan),
          f"only {kills_done}/{len(plan)} planned SIGKILLs landed "
          "(the run completed early — raise --segments or tighten "
          "the plan)")

    # gate 1: fsck clean
    rep = fsck(os.path.join(soak_dir, "manifest.jsonl"),
               os.path.join(soak_dir, "ck.json"))
    check(rep["clean"], f"fsck NOT clean after recovery: "
          f"errors={rep['errors']} loss={rep['loss']}")

    # gate 2: no orphan temps survive recovery
    orphans = [f for f in os.listdir(soak_dir)
               if f.endswith(".srtb_tmp")]
    check(not orphans, f"orphan temp files survive: {orphans}")

    # gate 3: the union of outputs across all lives of the run is
    # bit-identical to the golden run — no duplicates, no loss
    soak_map = snapshot_outputs(soak_dir)
    missing = sorted(set(golden_map) - set(soak_map))
    extra = sorted(set(soak_map) - set(golden_map))
    check(not missing, f"artifacts LOST across crashes: {missing}")
    check(not extra, f"duplicate/unknown artifacts after crashes: "
          f"{extra}")
    differing = sorted(k for k in golden_map
                       if golden_map[k] != soak_map[k])
    check(not differing,
          f"artifact bytes differ from the golden run: {differing}")

    # gate 4: file mode never sheds — any drop would be silent loss
    dropped = sum(int(r["stats"].get("segments_dropped", 0))
                  for r in all_res if r["stats"])
    check(dropped == 0, f"file-mode soak dropped {dropped} segment(s)")

    # recovery bookkeeping across every life of the run (recovery
    # markers print at child startup, so killed children count too)
    replayed = sum(int(r["replayed_skips"]) for r in all_res)
    recovered = sum(int(r["recovery"]["recovered_segments"])
                    for r in all_res if r["recovery"])
    rolled = sum(int(r["recovery"]["rolled_back_intents"])
                 for r in all_res if r["recovery"])

    # gate 5: the steered windows provably exercised their recovery
    # paths — a kill that left a sealed group beyond the checkpoint
    # must surface as a manifest replay-skip on resume, a mid-rename
    # kill as a rolled-back intent
    if expect_replay:
        check(replayed >= 1,
              "a kill left a committed segment beyond the checkpoint "
              "but no resumed child replay-skipped it")
    if expect_rollback:
        check(rolled >= 1,
              "a mid-rename kill landed but recovery rolled back "
              "no uncommitted intent")

    return {
        "seed": seed, "segments": total_segments,
        "artifacts": len(golden_map),
        "plan": [f"{k}@{a}" for k, a in plan],
        "sigkills": kills_done, "resumes": resumes + 1,
        "replayed_skips": replayed,
        "recovered_segments": recovered,
        "rolled_back_intents": rolled,
        "fsck_records": rep["records"],
        "ok": True,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="crash-soak",
        description="SIGKILL crash soak for durable exactly-once "
                    "outputs (see srtb_tpu/tools/crash_soak.py)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--segments", type=int, default=10)
    ap.add_argument("--kills", type=int, default=5)
    ap.add_argument("--log2n", type=int, default=13)
    ap.add_argument("--kill-plan", default=None,
                    help="explicit plan 'kind@arg,...' (kinds "
                         "ckpt_stall|sink_stall|rename); overrides "
                         "--kills generation")
    ap.add_argument("--writer-threads", type=int, default=0,
                    help="candidate-writer pool size in the children "
                         "(0 = synchronous writes)")
    # child-process plumbing (not for interactive use)
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--stall-rename-at", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--stall-s", type=float, default=STALL_S,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child:
        return _child_main(args.child, args.stall_rename_at,
                           args.stall_s)

    try:
        report = run_soak(seed=args.seed, segments=args.segments,
                          kills=args.kills, log2n=args.log2n,
                          kill_plan=args.kill_plan,
                          writer_threads=args.writer_threads)
    except SoakFailure as e:
        print(json.dumps({"ok": False, "failure": str(e)}))
        print(f"crash-soak: GATE FAILED — {e}", file=sys.stderr)
        return 1
    print(json.dumps(report, sort_keys=True))
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
