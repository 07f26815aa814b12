"""Fleet chaos soak: cross-stream blast-radius gate.

The multi-tenant contract of :mod:`srtb_tpu.pipeline.fleet` is that a
faulty stream's blast radius is exactly itself.  This harness proves
it end-to-end: N seeded streams (distinct baseband, shared plan
family) run (1) each SOLO through the single-stream ``Pipeline`` —
the golden reference — and then (2) together through a
``StreamFleet`` with a fault plan injected into ONE victim stream
(stream-selector scoped, e.g. ``victim:dispatch:oom@1``).  The gate:

- **(a) healthy isolation**: every healthy stream's final output set
  (relative paths + SHA-256) is BIT-identical to its solo golden run
  — scheduling N tenants onto one device, with a neighbor faulting,
  changed nothing for the innocent;
- **(b) victim accounting**: the victim's loss is accounted-only
  (drained + dropped == source segments, nothing vanishes), its
  detection DECISIONS match its solo run exactly (recovery may change
  the plan, never the science), and the demotions/sheds are
  attributed to the victim's stream id in the v8 journal (healthy
  journals carry zero);
- **(c) shared plan economy**: the fleet's plan cache records exactly
  ONE compile for the shared plan family across all streams
  (``hits == N - 1``).

``--batch B`` runs the soak with cross-tenant continuous batching
armed (``fleet_batch_max=B``): the gate swaps healthy bit-identity
for the documented vmap contract (``.bin`` baseband still bitwise,
float artifacts — waterfall ``.npy``, time-series ``.tim`` —
``np.allclose``, detection DECISIONS still exact) and adds the
batching-economy checks: journal records carry ``batch_size``, the
journal-derived device dispatch count is at most half the drained
segment count, and the victim's faults never retire a neighbor out
of the shared batch group.

``--selftest`` proves the gate is sharp: an UNSCOPED fault plan (no
stream selector — it arms in every lane) must FAIL the healthy-
journal attribution check, and a scoped single-oom run must pass.

``--ab`` instead runs the steady-state single-stream A/B (fleet
engine with N=1 vs the solo ``Pipeline``) and reports both medians —
the PERF.md round-15 measurement.

``--migrate`` runs the ELASTIC-POOL migration soak instead: a seeded
2-device virtual pool (``fleet_devices=2``), a mid-run scoped device
kill (``--kill-device IDX --kill-at K`` arms the pool's deterministic
virtual halt) or an operator rolling restart (``--rolling``).  The
gate: every victim lane resumes on the surviving member and its final
output set (relative paths + SHA-256) is BIT-identical to its solo
golden, loss is zero (the in-flight window re-dispatches cold from
retained host buffers), the ingest ring records exactly ONE extra
cold dispatch per migration (``ring_cold_dispatches == streams +
migrations``), the journal is v11 with every record device-stamped
and victim journals ending on the survivor's label, and — the scoped
HALT-domain pin — the pool records exactly one compile per member
with zero healthy-lane demotions, recompiles or fleet-wide reinits.

Usage::

    python -m srtb_tpu.tools.fleet_soak [--streams N] [--segments N]
        [--log2n N] [--plan PLAN] [--batch B] [--selftest]
        [--ab [--reps R]]
        [--migrate [--kill-device IDX] [--kill-at K] [--rolling]]

Exit 0 on a passing gate (or sharp selftest), 1 on any failure.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

import numpy as np

from srtb_tpu.tools import SOAK_DM


class SoakFailure(AssertionError):
    """One broken fleet invariant (the gate)."""


def _stream_names(n: int) -> list[str]:
    # stream0 is always the victim (matching the default --plan)
    return [f"stream{i}" for i in range(n)]


def make_deterministic_source(cfg):
    """File source with offset-derived timestamps, so artifact names
    reproduce across the solo and fleet runs (same convention as
    tools/crash_soak.py)."""
    from srtb_tpu.io.file_input import BasebandFileReader

    class DeterministicTimestampReader(BasebandFileReader):
        def __next__(self):
            offset = self.logical_offset
            work = super().__next__()
            work.timestamp = 1_700_000_000_000_000_000 + offset
            return work

    return DeterministicTimestampReader(cfg)


def _cfg(tmp: str, name: str, run_dir: str, n: int, **extra):
    from srtb_tpu.config import Config
    base = dict(
        baseband_input_count=n, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6, dm=SOAK_DM,
        input_file_path=os.path.join(tmp, f"bb_{name}.bin"),
        baseband_output_file_prefix=os.path.join(run_dir, "out_"),
        spectrum_channel_count=64,
        # every segment must write artifacts (deterministically) so
        # the bit-identical union is a real comparison, not vacuous
        mitigate_rfi_average_method_threshold=1000.0,
        mitigate_rfi_spectral_kurtosis_threshold=50.0,
        signal_detect_signal_noise_threshold=1.5,
        signal_detect_max_boxcar_length=8,
        baseband_reserve_sample=True,
        writer_thread_count=0,
        fft_strategy="four_step",
        inflight_segments=2,
        retry_backoff_base_s=0.001,
        checkpoint_path=os.path.join(run_dir, "ck.json"),
        run_manifest_path=os.path.join(run_dir, "manifest.jsonl"),
    )
    base.update(extra)
    return Config(**base)


def _synthesize(tmp: str, names: list[str], n: int, segments: int,
                seed: int) -> None:
    from srtb_tpu.io.synth import make_dispersed_baseband
    for i, name in enumerate(names):
        make_dispersed_baseband(
            n * segments, 1405.0, 64.0, SOAK_DM,
            pulse_positions=[n // 2 + j * n for j in range(segments)],
            pulse_amp=30.0, nbits=8, seed=seed * 1000 + i,
        ).tofile(os.path.join(tmp, f"bb_{name}.bin"))


class _DecisionTap:
    """Pass-through sink recording detection decisions (rides NEXT TO
    the real writer sinks, so artifacts still land on disk)."""

    wants_waterfall = False

    def __init__(self):
        self.out = []

    def push(self, work, positive):
        det = work.detect
        self.out.append((np.asarray(det.signal_counts).copy(),
                         np.asarray(det.zero_count).copy(),
                         bool(positive)))


# the documented vmap tolerance (the archive micro-batch precedent,
# tools/archive_replay.py): batching stacks segments into one vmapped
# program, which may reassociate float32 reductions — detection
# decisions and .bin baseband bytes stay exact, float artifacts stay
# numerically close with an amplitude-relative absolute term
VMAP_RTOL = 1e-5
VMAP_ATOL_FRAC = 1e-4


def _load_float(path: str):
    """Float artifact loader for the vmap-tolerance comparison; None
    for artifact kinds that have no float representation."""
    if path.endswith(".npy"):
        return np.load(path)
    if path.endswith(".tim"):
        return np.fromfile(path, dtype=np.float32)
    return None


def _artifacts_close(solo_dir: str, fleet_dir: str, solo_map: dict,
                     fleet_map: dict) -> str | None:
    """Batched-mode output comparison: identical relative-name sets,
    ``.bin`` bitwise, float artifacts within the vmap tolerance.
    Returns a failure description, or None when the gate holds."""
    if set(fleet_map) != set(solo_map):
        return (f"output name sets differ (fleet {sorted(fleet_map)} "
                f"vs solo {sorted(solo_map)})")
    for rel in sorted(solo_map):
        if fleet_map[rel] == solo_map[rel]:
            continue  # bitwise identical — always acceptable
        if rel.endswith(".bin"):
            return (f"{rel}: baseband .bin bytes differ (batching "
                    "must not touch raw capture)")
        a = _load_float(os.path.join(fleet_dir, rel))
        b = _load_float(os.path.join(solo_dir, rel))
        if a is None or b is None:
            return f"{rel}: differs and is not a float artifact"
        atol = VMAP_ATOL_FRAC * max(float(np.abs(b).max()), 1.0)
        if a.shape != b.shape or not np.allclose(
                a, b, rtol=VMAP_RTOL, atol=atol):
            return (f"{rel}: float artifact outside the vmap "
                    f"tolerance (rtol={VMAP_RTOL}, atol={atol:g})")
    return None


def _solo_run(cfg) -> tuple:
    """One golden single-stream run; returns (stats, decisions)."""
    from srtb_tpu.io.writers import WriteSignalSink
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.utils.metrics import metrics
    metrics.reset()
    tap = _DecisionTap()
    sinks = [WriteSignalSink(cfg), tap]
    with Pipeline(cfg, source=make_deterministic_source(cfg),
                  sinks=sinks) as pipe:
        stats = pipe.run()
    return stats, tap.out


def run_soak(streams: int = 3, segments: int = 5, log2n: int = 13,
             plan: str | None = None, seed: int = 0,
             tmpdir: str | None = None, batch: int = 0,
             extra_cfg: dict | None = None) -> dict:
    """One full soak (solo goldens + fleet run + the gate).  Returns
    the report dict; raises :class:`SoakFailure` on any broken
    invariant.  ``batch >= 2`` arms cross-tenant continuous batching
    (``fleet_batch_max=batch``) and swaps healthy bit-identity for
    the vmap-tolerance contract plus the batching-economy checks.
    ``extra_cfg`` overrides land on the FLEET lanes only (the solo
    goldens stay canonical) — race_soak uses it to arm ``tsan=1``."""
    from srtb_tpu.io.writers import WriteSignalSink
    from srtb_tpu.pipeline.fleet import StreamFleet, StreamSpec
    from srtb_tpu.resilience.faults import parse_plan
    from srtb_tpu.tools.crash_soak import snapshot_outputs
    from srtb_tpu.utils.metrics import metrics

    tmp = tmpdir or tempfile.mkdtemp(prefix="srtb_fleet_")
    n = 1 << log2n
    batch = max(0, int(batch))
    names = _stream_names(streams)
    victim = names[0]
    if plan is None:
        plan = (f"{victim}:dispatch:oom@1,"
                f"{victim}:sink_write:raise@2,"
                f"{victim}:fetch:stall=0.05@3")
    specs_parsed = parse_plan(plan)
    victims = {s.stream for s in specs_parsed if s.stream is not None}
    n_demote = sum(1 for s in specs_parsed
                   if s.action in ("oom", "compile_fail"))
    _synthesize(tmp, names, n, segments, seed)

    # ---- solo goldens (per-stream run dirs, identical rel names)
    solo_out: dict[str, dict] = {}
    solo_dec: dict[str, list] = {}
    solo_segs: dict[str, int] = {}
    for name in names:
        run_dir = os.path.join(tmp, f"solo_{name}")
        os.makedirs(run_dir, exist_ok=True)
        stats, dec = _solo_run(_cfg(tmp, name, run_dir, n))
        solo_out[name] = snapshot_outputs(run_dir)
        solo_dec[name] = dec
        # overlap-save re-reads reserved tails, so the stream yields
        # MORE segments than the synthesized count — the solo run is
        # the authority on how many a lossless run drains
        solo_segs[name] = int(stats.segments)
        if not solo_out[name]:
            raise SoakFailure(
                f"solo run of {name} wrote NO artifacts — the "
                "bit-identical gate would be vacuous")

    # ---- fleet run, victim faulted
    metrics.reset()
    specs = []
    taps: dict[str, _DecisionTap] = {}
    jpaths: dict[str, str] = {}
    for name in names:
        run_dir = os.path.join(tmp, f"fleet_{name}")
        os.makedirs(run_dir, exist_ok=True)
        jpaths[name] = os.path.join(tmp, f"journal_{name}.jsonl")
        cfg = _cfg(tmp, name, run_dir, n, fault_plan=plan,
                   telemetry_journal_path=jpaths[name],
                   fleet_batch_max=batch, **(extra_cfg or {}))
        taps[name] = _DecisionTap()
        specs.append(StreamSpec(
            name=name, cfg=cfg,
            source=make_deterministic_source(cfg),
            sinks=[WriteSignalSink(cfg), taps[name]]))
    fleet = StreamFleet(specs)
    results = fleet.run()
    fleet.close()
    compiles, hits = fleet.plans.compiles, fleet.plans.hits
    dropped_by = metrics.by_label("segments_dropped")

    def check(cond, msg):
        if not cond:
            raise SoakFailure(msg)

    for name in names:
        check(results[name].status == "done",
              f"stream {name} did not finish: {results[name].status} "
              f"({results[name].error!r})")

    # (a) healthy streams: outputs equal to solo — bit-identical when
    # batching is off, the vmap-tolerance contract when it is on
    # (batching folds several tenants into one vmapped dispatch, so
    # float artifacts may differ in the last bits; .bin baseband and
    # detection decisions must not)
    for name in names:
        if name in victims:
            continue
        fleet_dir = os.path.join(tmp, f"fleet_{name}")
        fleet_set = snapshot_outputs(fleet_dir)
        if batch >= 2:
            why = _artifacts_close(os.path.join(tmp, f"solo_{name}"),
                                   fleet_dir, solo_out[name],
                                   fleet_set)
            check(why is None,
                  f"healthy stream {name} (batched): {why}")
        else:
            check(fleet_set == solo_out[name],
                  f"healthy stream {name}: fleet output set differs "
                  f"from its solo golden run (fleet "
                  f"{sorted(fleet_set)} vs solo "
                  f"{sorted(solo_out[name])})")
        for i, (a, b) in enumerate(zip(taps[name].out,
                                       solo_dec[name])):
            check(np.array_equal(a[0], b[0])
                  and np.array_equal(a[1], b[1]) and a[2] == b[2],
                  f"healthy stream {name}: decision differs at "
                  f"segment {i}")

    # (b) victim: accounted-only loss, decisions exact, journal
    # attribution
    for name in victims:
        res = results[name]
        vdropped = int(dropped_by.get(name, 0))
        check(res.drained + vdropped == solo_segs[name],
              f"victim {name}: loss not accounted — {res.drained} "
              f"drained + {vdropped} dropped != {solo_segs[name]} "
              "source segments")
        for i, (a, b) in enumerate(zip(taps[name].out,
                                       solo_dec[name])):
            check(np.array_equal(a[0], b[0])
                  and np.array_equal(a[1], b[1]) and a[2] == b[2],
                  f"victim {name}: detection decision differs at "
                  f"segment {i} (recovery changed the science)")
    recs_by: dict[str, list] = {}
    for name in names:
        recs = [json.loads(line) for line in open(jpaths[name])
                if line.strip().startswith("{")]
        recs_by[name] = recs
        check(recs and all(r.get("stream") == name and r["v"] == 11
                           for r in recs),
              f"stream {name}: journal records not stream-stamped")
        total_demote = int(recs[-1].get("plan_demotions", 0))
        if name in victims:
            check(total_demote == n_demote,
                  f"victim {name}: journal plan_demotions "
                  f"{total_demote} != {n_demote} injected")
        else:
            check(total_demote == 0,
                  f"healthy stream {name}: journal attributes "
                  f"{total_demote} demotions — the victim's fault "
                  "leaked into a neighbor's books")

    # (d) batching economy (batched soak only): every drained segment
    # is journaled, batched ones carry batch_size, and the implied
    # device dispatch count — each record contributes 1/batch_size of
    # a dispatch — shows real cross-tenant amortization
    batched_dispatches = int(metrics.get("batched_dispatches"))
    batched_segments = int(metrics.get("batched_segments"))
    dispatch_est = 0.0
    total_recs = 0
    for name in names:
        for r in recs_by[name]:
            total_recs += 1
            b = int(r.get("batch_size", 1) or 1)
            check(b >= 1, f"stream {name}: journal batch_size {b}")
            dispatch_est += 1.0 / b
    dispatch_est = round(dispatch_est)
    if batch >= 2:
        check(batched_dispatches >= 1,
              "batched soak recorded no batched_dispatches — the "
              "batch former never fired")
        check(batched_segments >= 2 * batched_dispatches,
              f"batched_segments {batched_segments} < 2x "
              f"batched_dispatches {batched_dispatches}")
        check(dispatch_est * 2 <= total_recs,
              f"journal-implied device dispatches {dispatch_est} > "
              f"half of {total_recs} drained segments — batching "
              "amortized too little")
    else:
        check(batched_dispatches == 0 and all(
                  "batch_size" not in r
                  for name in names for r in recs_by[name]),
              "unbatched soak journaled batch_size fields")

    # (c) shared plan cache: one compile per family
    check(compiles == 1,
          f"plan cache recorded {compiles} compiles for one shared "
          "plan family (expected exactly 1)")
    check(hits == streams - 1,
          f"plan cache hits {hits} != {streams - 1} "
          "(every non-first stream must reuse the shared plan)")

    return {
        "streams": streams, "segments": segments, "plan": plan,
        "victims": sorted(victims),
        "drained": {k: results[k].drained for k in names},
        "dropped": {k: int(dropped_by.get(k, 0)) for k in names},
        "plan_compiles": compiles, "plan_cache_hits": hits,
        "fleet_batch_max": batch,
        "batched_dispatches": batched_dispatches,
        "batched_segments": batched_segments,
        "device_dispatches_est": dispatch_est,
        "journaled_segments": total_recs,
        "ok": True,
    }


def selftest(log2n: int = 12) -> list[str]:
    """Prove the gate is sharp.  (a) an UNSCOPED oom (no stream
    selector) arms in every lane, so healthy lanes demote too and the
    journal-attribution check must fail; (b) the scoped default plan
    must pass (the gate is not simply failing everything)."""
    failures = []
    try:
        run_soak(streams=2, segments=3, log2n=log2n,
                 plan="dispatch:oom@1")
        failures.append(
            "gate passed an UNSCOPED fault plan — cross-stream "
            "fault leakage went unnoticed")
    except SoakFailure:
        pass  # caught, as required
    try:
        run_soak(streams=2, segments=3, log2n=log2n,
                 plan="stream0:dispatch:oom@1")
    except Exception as e:  # noqa: BLE001 - reported, not raised
        failures.append(f"scoped single-oom soak did not pass: {e!r}")
    return failures


def run_migrate(streams: int = 3, segments: int = 6, log2n: int = 13,
                seed: int = 0, kill_device: int = 1, kill_at: int = 2,
                rolling: bool = False, tmpdir: str | None = None,
                extra_cfg: dict | None = None) -> dict:
    """Elastic-pool migration soak: solo goldens, then the same
    streams on a seeded 2-device VIRTUAL pool with either a scoped
    mid-run device kill (driver (a): the pool's deterministic
    ``schedule_halt``) or an operator rolling restart (driver (c)).
    Lanes run with ``inflight_segments=1`` so the cold-dispatch
    arithmetic is exact: one ring cold per lane start plus exactly
    one per migration.  ``extra_cfg`` overrides land on the FLEET
    lanes only (race_soak arms ``tsan=1`` there).  Raises
    :class:`SoakFailure` on any broken invariant; returns the report
    dict."""
    import threading
    import time as _time

    from srtb_tpu.io.writers import WriteSignalSink
    from srtb_tpu.pipeline.fleet import StreamFleet, StreamSpec
    from srtb_tpu.tools.crash_soak import snapshot_outputs
    from srtb_tpu.utils import termination
    from srtb_tpu.utils.metrics import metrics

    tmp = tmpdir or tempfile.mkdtemp(prefix="srtb_migrate_")
    n = 1 << log2n
    names = _stream_names(streams)
    _synthesize(tmp, names, n, segments, seed)

    # ---- solo goldens (inflight 1, matching the fleet lanes)
    solo_out: dict[str, dict] = {}
    solo_dec: dict[str, list] = {}
    solo_segs: dict[str, int] = {}
    for name in names:
        run_dir = os.path.join(tmp, f"solo_{name}")
        os.makedirs(run_dir, exist_ok=True)
        stats, dec = _solo_run(
            _cfg(tmp, name, run_dir, n, inflight_segments=1))
        solo_out[name] = snapshot_outputs(run_dir)
        solo_dec[name] = dec
        solo_segs[name] = int(stats.segments)
        if not solo_out[name]:
            raise SoakFailure(
                f"solo run of {name} wrote NO artifacts — the "
                "bit-identical gate would be vacuous")

    # ---- fleet run on the 2-device virtual pool
    metrics.reset()
    specs = []
    taps: dict[str, _DecisionTap] = {}
    jpaths: dict[str, str] = {}
    for name in names:
        run_dir = os.path.join(tmp, f"fleet_{name}")
        os.makedirs(run_dir, exist_ok=True)
        jpaths[name] = os.path.join(tmp, f"journal_{name}.jsonl")
        cfg = _cfg(tmp, name, run_dir, n, fleet_devices=2,
                   inflight_segments=1,
                   telemetry_journal_path=jpaths[name],
                   **(extra_cfg or {}))
        taps[name] = _DecisionTap()
        specs.append(StreamSpec(
            name=name, cfg=cfg,
            source=make_deterministic_source(cfg),
            sinks=[WriteSignalSink(cfg), taps[name]]))
    fleet = StreamFleet(specs)
    pool_size = len(fleet.pool)
    if pool_size != 2:
        raise SoakFailure(
            f"fleet built a {pool_size}-member pool (fleet_devices=2 "
            "requested) — the migration soak needs a 2-device pool")
    trigger: threading.Thread | None = None
    fired = threading.Event()
    if rolling:
        # operator path: a tagged side thread waits for steady state
        # (a few dispatches landed) then queues the rolling restart —
        # the scheduler thread does the actual drains
        def _roll_trigger():
            while not fired.is_set():
                if fleet.pool.total_dispatches >= max(1, kill_at):
                    fleet.rolling_restart()
                    fired.set()
                    return
                _time.sleep(0.001)
        trigger = threading.Thread(
            target=_roll_trigger, name="migrate-soak-roll",
            daemon=True)
        termination.tag_thread(trigger)
        trigger.start()
    else:
        fleet.pool.schedule_halt(kill_device,
                                 after_dispatches=max(1, kill_at))
    results = fleet.run()
    pool_compiles = fleet.pool.compiles
    if trigger is not None:
        fired.set()
        trigger.join(timeout=10)
    fleet.close()
    dropped_by = metrics.by_label("segments_dropped")
    migs = int(metrics.get("migrations"))
    drains = int(metrics.get("device_drains"))
    ring_cold = int(metrics.get("ring_cold_dispatches"))

    def check(cond, msg):
        if not cond:
            raise SoakFailure(msg)

    for name in names:
        check(results[name].status == "done",
              f"stream {name} did not finish: {results[name].status} "
              f"({results[name].error!r})")

    # (a) lossless resume: zero drops, every source segment drained
    for name in names:
        vdropped = int(dropped_by.get(name, 0))
        check(vdropped == 0,
              f"stream {name}: {vdropped} segment(s) dropped — "
              "migration must be lossless (cold re-dispatch, not "
              "shed)")
        check(results[name].drained == solo_segs[name],
              f"stream {name}: drained {results[name].drained} != "
              f"{solo_segs[name]} solo source segments")

    # (b) bit-identity for EVERY stream — victims included: the
    # migrated lane's outputs (paths + SHA-256) and detection
    # decisions match its solo golden exactly
    for name in names:
        fleet_set = snapshot_outputs(os.path.join(tmp, f"fleet_{name}"))
        check(fleet_set == solo_out[name],
              f"stream {name}: fleet output set differs from its "
              f"solo golden (fleet {sorted(fleet_set)} vs solo "
              f"{sorted(solo_out[name])})")
        check(len(taps[name].out) == len(solo_dec[name]),
              f"stream {name}: {len(taps[name].out)} decisions vs "
              f"{len(solo_dec[name])} solo")
        for i, (a, b) in enumerate(zip(taps[name].out,
                                       solo_dec[name])):
            check(np.array_equal(a[0], b[0])
                  and np.array_equal(a[1], b[1]) and a[2] == b[2],
                  f"stream {name}: decision differs at segment {i} "
                  "(migration changed the science)")

    # (c) migration accounting: drivers fired, victims resumed on the
    # survivor, exactly one extra ring cold dispatch per migration
    per_lane_migs = {name: int(results[name].extras.get(
        "migrations", 0)) for name in names}
    check(migs >= 1,
          "no migration happened — the kill/rolling driver never "
          "fired (did the run finish before the trigger?)")
    check(migs == sum(per_lane_migs.values()),
          f"migrations counter {migs} != per-lane sum "
          f"{sum(per_lane_migs.values())}")
    check(ring_cold == streams + migs,
          f"ring_cold_dispatches {ring_cold} != {streams} lane "
          f"starts + {migs} migrations — a migration must cost "
          "EXACTLY one cold re-arm")
    if rolling:
        check(fired.is_set(), "rolling trigger thread never fired")
        check(drains == pool_size,
              f"device_drains {drains} != {pool_size} pool members "
              "(rolling restart drains each member once)")
    else:
        killed = fleet.pool.devices[kill_device].label
        check(drains == 1,
              f"device_drains {drains} != 1 (one scoped kill)")
        victims = [n for n in names if per_lane_migs[n] > 0]
        check(victims,
              "scoped kill produced no victim lanes — nothing was "
              f"placed on {killed}?")
        for name in victims:
            check(results[name].extras.get("device") != killed,
                  f"victim {name} finished on {killed} — it never "
                  "resumed on the survivor")
        # the scoped HALT-domain pin: one compile per member, no
        # survivor recompile (migrants REJOIN the survivor's plan
        # family), no demotions, no fleet-wide reinit
        check(pool_compiles == pool_size,
              f"pool recorded {pool_compiles} compiles for "
              f"{pool_size} members — a scoped halt must not "
              "recompile the survivor's plans")
    check(int(metrics.get("device_reinits")) == 0,
          "a scoped device halt escalated to a fleet-wide reinit")
    check(int(metrics.get("plan_demotions")) == 0,
          "migration demoted a lane's plan — resume must rejoin the "
          "target's shared family at rung 0")

    # (d) journal: v11, every record device-stamped, victim journals
    # END on a surviving member's label
    killed_label = (None if rolling
                    else fleet.pool.devices[kill_device].label)
    for name in names:
        recs = [json.loads(line) for line in open(jpaths[name])
                if line.strip().startswith("{")]
        check(recs and all(r["v"] == 11 and r.get("device")
                           for r in recs),
              f"stream {name}: journal records missing v11 device "
              "stamps")
        check(len(recs) == solo_segs[name],
              f"stream {name}: {len(recs)} journal records != "
              f"{solo_segs[name]} drained segments")
        if killed_label is not None and per_lane_migs[name] > 0:
            check(recs[-1]["device"] != killed_label,
                  f"victim {name}: journal ends on the KILLED member "
                  f"{killed_label}")
            check(len({r["device"] for r in recs}) >= 2,
                  f"victim {name}: journal never switched device "
                  "labels across the migration boundary")

    return {
        "streams": streams, "segments": segments,
        "mode": "rolling" if rolling else "kill",
        "kill_device": None if rolling else kill_device,
        "kill_at": kill_at, "migrations": migs,
        "per_lane_migrations": per_lane_migs,
        "device_drains": drains,
        "ring_cold_dispatches": ring_cold,
        "pool_compiles": pool_compiles,
        "drained": {k: results[k].drained for k in names},
        "ok": True,
    }


def run_ab(segments: int = 20, log2n: int = 13, reps: int = 3) -> dict:
    """Steady-state single-stream A/B: fleet engine with N=1 vs the
    solo Pipeline, same config/data, median-of-reps seg/s each."""
    import time

    from srtb_tpu.io.writers import WriteSignalSink
    from srtb_tpu.pipeline.fleet import StreamFleet, StreamSpec
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.utils.metrics import metrics

    tmp = tempfile.mkdtemp(prefix="srtb_fleet_ab_")
    n = 1 << log2n
    _synthesize(tmp, ["ab"], n, segments, seed=0)

    def one_solo() -> float:
        run_dir = tempfile.mkdtemp(dir=tmp)
        cfg = _cfg(tmp, "ab", run_dir, n, checkpoint_path="",
                   run_manifest_path="")
        metrics.reset()
        t0 = time.perf_counter()
        with Pipeline(cfg, source=make_deterministic_source(cfg),
                      sinks=[WriteSignalSink(cfg)]) as pipe:
            stats = pipe.run()
        return stats.segments / (time.perf_counter() - t0)

    def one_fleet() -> float:
        run_dir = tempfile.mkdtemp(dir=tmp)
        cfg = _cfg(tmp, "ab", run_dir, n, checkpoint_path="",
                   run_manifest_path="")
        metrics.reset()
        t0 = time.perf_counter()
        fleet = StreamFleet([StreamSpec(
            name="ab", cfg=cfg, source=make_deterministic_source(cfg),
            sinks=[WriteSignalSink(cfg)])])
        res = fleet.run()
        fleet.close()
        return res["ab"].drained / (time.perf_counter() - t0)

    solo = sorted(one_solo() for _ in range(reps))[reps // 2]
    fleet = sorted(one_fleet() for _ in range(reps))[reps // 2]
    return {"solo_seg_per_s": round(solo, 2),
            "fleet_n1_seg_per_s": round(fleet, 2),
            "delta_pct": round((fleet - solo) / solo * 100, 2),
            "segments": segments, "log2n": log2n, "reps": reps}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="fleet-soak",
        description="multi-tenant fleet blast-radius gate "
                    "(see srtb_tpu/tools/fleet_soak.py)")
    ap.add_argument("--streams", type=int, default=3)
    ap.add_argument("--segments", type=int, default=5)
    ap.add_argument("--log2n", type=int, default=13)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan", default=None,
                    help="explicit fault plan (stream-selector scoped;"
                         " default faults stream0)")
    ap.add_argument("--batch", type=int, default=0,
                    help="fleet_batch_max for a batched soak (>= 2 "
                         "arms cross-tenant continuous batching; the "
                         "gate switches to the vmap-tolerance "
                         "contract + batching-economy checks)")
    ap.add_argument("--selftest", action="store_true",
                    help="prove the gate catches cross-stream leakage")
    ap.add_argument("--ab", action="store_true",
                    help="single-stream A/B: fleet N=1 vs Pipeline")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--migrate", action="store_true",
                    help="elastic-pool migration soak: 2-device "
                         "virtual pool, scoped mid-run device kill "
                         "(or --rolling), bit-identical resume gate")
    ap.add_argument("--kill-device", type=int, default=1,
                    help="pool member index the scheduled halt kills")
    ap.add_argument("--kill-at", type=int, default=2,
                    help="member dispatch count the halt fires after "
                         "(rolling: pool dispatch count that triggers "
                         "the restart)")
    ap.add_argument("--rolling", action="store_true",
                    help="drive migration via an operator rolling "
                         "restart instead of a device kill")
    args = ap.parse_args(argv)

    if args.selftest:
        fails = selftest()
        for f in fails:
            print(f"fleet-soak selftest: {f}", file=sys.stderr)
        print("fleet-soak selftest: "
              + ("FAILED" if fails else
                 "OK — cross-stream leakage fails the gate"))
        return 1 if fails else 0
    if args.ab:
        print(json.dumps(run_ab(segments=args.segments * 4,
                                log2n=args.log2n, reps=args.reps),
                         sort_keys=True))
        return 0
    if args.migrate:
        try:
            report = run_migrate(
                streams=args.streams, segments=args.segments,
                log2n=args.log2n, seed=args.seed,
                kill_device=args.kill_device, kill_at=args.kill_at,
                rolling=args.rolling)
        except SoakFailure as e:
            print(json.dumps({"ok": False, "failure": str(e)}))
            print(f"fleet-soak: MIGRATION GATE FAILED — {e}",
                  file=sys.stderr)
            return 1
        print(json.dumps(report, sort_keys=True))
        return 0
    try:
        report = run_soak(streams=args.streams, segments=args.segments,
                          log2n=args.log2n, plan=args.plan,
                          seed=args.seed, batch=args.batch)
    except SoakFailure as e:
        print(json.dumps({"ok": False, "failure": str(e)}))
        print(f"fleet-soak: GATE FAILED — {e}", file=sys.stderr)
        return 1
    print(json.dumps(report, sort_keys=True))
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
