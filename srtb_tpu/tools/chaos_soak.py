"""Chaos soak: seeded randomized device-fault runs with an
accounted-loss-only gate.

``udp_soak --fault-plan`` injects ONE hand-written plan; this harness
*generates* fault plans from a seed (site x action x segment index,
including the device-fault classes ``oom`` / ``compile_fail`` /
``device_halt`` that exercise the self-healing compute ladder,
resilience/demote.py) and runs the full pipeline end-to-end three
times:

1. **clean, ladder off** — the reference output;
2. **clean, ladder armed** — must be BIT-identical to (1): arming the
   self-healing machinery on a healthy run costs nothing and changes
   nothing (the zero-cost-off acceptance);
3. **chaos** — the generated plan injected, healing armed.

The gate then asserts the self-healing contract:

- the run completes and every planned fault actually fired;
- loss is accounted-only: every source segment is either drained or
  counted in ``segments_dropped`` (nothing vanishes);
- every drained segment's detection DECISIONS (signal counts, zapped-
  channel counts, positives) equal the clean run's exactly, and the
  detection time series matches within the demoted plans' documented
  tolerance (the fused/unfused/staged/monolithic parity bounds of
  tests/test_fusion.py) — recovery may change the plan, never the
  science;
- the recovery counters match the injected plan EXACTLY:
  ``plan_demotions`` == injected oom+compile faults,
  ``device_reinits`` == injected halts, and the retry counter covers
  the transient injections — silent recovery is indistinguishable
  from a pipeline that never faults, so the soak demands the books
  balance to the fault.

``--selftest`` proves the gate itself is sharp: a fault class the
healer does NOT handle (an injected fatal; a device fault with
healing disabled) must fail the soak, not pass it.

Pool-scoped halts: a plan entry ``device:halt@K`` is NOT a pipeline
fault-injector spec — it schedules the elastic pool's deterministic
virtual halt (``pipeline/pool.py``) on one pool member after K of
ITS dispatches.  Entries are stripped from the pipeline plan and run
as a fourth phase: one stream on a ``len(entries)+1``-member virtual
pool, entry i armed on member i, so every halt has a survivor to
drain onto.  The gate: the run completes with zero loss, decisions
(and time series — migration stays at rung 0) BIT-equal the clean
reference, ``device_drains`` matches the scheduled halts exactly,
every halt produced a live migration, and no halt escalated to a
fleet-wide reinit.

Usage::

    python -m srtb_tpu.tools.chaos_soak [--seed N] [--segments N]
        [--faults N] [--plan PLAN] [--log2n N] [--promote-after N]
        [--selftest]

Exit 0 on a passing soak (or sharp selftest), 1 on any gate failure.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile

import numpy as np

from srtb_tpu.tools import SOAK_DM

# actions the generator may schedule, with rough weights: device
# faults are the point of this harness, the PR-4 classes keep their
# recovery paths soaked alongside
_ACTIONS = ("oom", "compile_fail", "device_halt", "raise", "corrupt",
            "stall")
_WEIGHTS = (3, 3, 2, 2, 1, 1)
_DEVICE = ("oom", "compile_fail", "device_halt")
_DEVICE_SITES = ("h2d", "dispatch", "fetch")
_HOST_SITES = ("ingest", "h2d", "dispatch", "fetch", "sink_write",
               "checkpoint")


class SoakFailure(AssertionError):
    """One broken soak invariant (the gate)."""


def _base_cfg(tmp: str, n: int, tag: str, **extra):
    from srtb_tpu.config import Config
    return Config(
        baseband_input_count=n, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6, dm=SOAK_DM,
        input_file_path=os.path.join(tmp, "bb.bin"),
        baseband_output_file_prefix=os.path.join(tmp, tag + "_"),
        spectrum_channel_count=64,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=True,  # overlap-save: the ring rung is live
        writer_thread_count=0,
        fft_strategy="four_step",
        inflight_segments=2,
        retry_backoff_base_s=0.001,
        **extra)


def generate_plan(seed: int, segments: int, faults: int,
                  max_demotions: int, max_halts: int) -> str:
    """Seeded random fault plan: distinct (site, index) pairs, device
    actions only at device sites, demotable/halt fault counts capped
    so the configured ladder and reinit budget can absorb the whole
    plan (the gate asserts exact counter matches, which requires every
    injected fault to be recoverable by construction)."""
    rng = random.Random(seed)
    entries, used = [], set()
    demotions = halts = 0
    attempts = 0
    while len(entries) < faults and attempts < 200:
        attempts += 1
        action = rng.choices(_ACTIONS, weights=_WEIGHTS)[0]
        if action in ("oom", "compile_fail") \
                and demotions >= max_demotions:
            continue
        if action == "device_halt" and halts >= max_halts:
            continue
        site = rng.choice(_DEVICE_SITES if action in _DEVICE
                          else _HOST_SITES)
        # index >= 1 keeps the first segment clean (the cold dispatch
        # that arms the ring); < segments so every fault fires
        index = rng.randrange(1, segments)
        if (site, index) in used:
            continue
        used.add((site, index))
        if action in ("oom", "compile_fail"):
            demotions += 1
        elif action == "device_halt":
            halts += 1
        arg = "=0.05" if action == "stall" else ""
        entries.append(f"{site}:{action}{arg}@{index}")
    return ",".join(entries)


def _split_pool_plan(plan: str) -> tuple[list[int], str]:
    """Split ``device:halt@K`` pool-scoped entries out of a fault
    plan.  Returns (halt dispatch counts, remaining pipeline plan)."""
    halts, rest = [], []
    for ent in plan.split(","):
        ent = ent.strip()
        if not ent:
            continue
        if ent.startswith("device:halt@"):
            halts.append(int(ent.rsplit("@", 1)[1]))
        else:
            rest.append(ent)
    return halts, ",".join(rest)


class _CaptureSink:
    def __init__(self):
        self.out = []

    def push(self, work, positive):
        det = work.detect
        self.out.append((np.asarray(det.signal_counts).copy(),
                         np.asarray(det.zero_count).copy(),
                         np.asarray(det.time_series).copy(),
                         bool(positive)))


def _run(cfg, max_segments=None):
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.utils.metrics import metrics
    metrics.reset()
    sink = _CaptureSink()
    with Pipeline(cfg, sinks=[sink]) as pipe:
        stats = pipe.run(max_segments)
        unfired = pipe.faults.unfired() if pipe.faults else []
    counters = {k: metrics.get(k) for k in (
        "plan_demotions", "plan_promotions", "device_reinits",
        "retries_total", "segments_dropped", "data_loss_total",
        "faults_injected", "ring_cold_dispatches")}
    metrics.reset()
    return stats, sink, counters, unfired


def _run_pool_phase(tmp: str, n: int, pool_halts: list[int]) -> tuple:
    """The ``device:halt@K`` phase: one stream on a virtual pool with
    one member per scheduled halt plus a survivor; entry i arms member
    i's deterministic halt after K_i of its dispatches.  Returns
    (result, sink, counters)."""
    from srtb_tpu.pipeline.fleet import StreamFleet, StreamSpec
    from srtb_tpu.utils.metrics import metrics
    metrics.reset()
    members = len(pool_halts) + 1
    cfg = _base_cfg(tmp, n, "pool", fleet_devices=members)
    sink = _CaptureSink()
    fleet = StreamFleet([StreamSpec(name="chaos", cfg=cfg,
                                    sinks=[sink])])
    for i, k in enumerate(pool_halts):
        fleet.pool.schedule_halt(i, after_dispatches=k)
    results = fleet.run()
    fleet.close()
    counters = {k: int(metrics.get(k)) for k in (
        "device_drains", "migrations", "device_reinits",
        "segments_dropped", "plan_demotions")}
    metrics.reset()
    return results["chaos"], sink, counters


def run_soak(seed: int = 0, segments: int = 6, faults: int = 4,
             log2n: int = 14, plan: str | None = None,
             promote_after: int = 0, tmpdir: str | None = None) -> dict:
    """One full soak (three runs + the gate).  Returns the report
    dict; raises :class:`SoakFailure` on any broken invariant."""
    from srtb_tpu.io.synth import make_dispersed_baseband
    from srtb_tpu.resilience.demote import ladder_rungs
    from srtb_tpu.resilience.faults import parse_plan

    tmp = tmpdir or tempfile.mkdtemp(prefix="srtb_chaos_")
    n = 1 << log2n
    make_dispersed_baseband(
        n * segments, 1405.0, 64.0, SOAK_DM,
        pulse_positions=[n // 2 + i * n for i in range(segments)],
        pulse_amp=30.0, nbits=8, seed=seed,
    ).tofile(os.path.join(tmp, "bb.bin"))

    probe = _base_cfg(tmp, n, "probe")
    rungs = ladder_rungs(probe)
    if plan is None:
        plan = generate_plan(seed, segments, faults,
                             max_demotions=len(rungs), max_halts=3)
    # device:halt@K entries are POOL-scoped (pipeline/pool.py), not
    # fault-injector specs: strip them here, run them as phase 4
    pool_halts, pipe_plan = _split_pool_plan(plan)
    specs = parse_plan(pipe_plan) if pipe_plan else []
    n_demote = sum(1 for s in specs
                   if s.action in ("oom", "compile_fail"))
    n_halt = sum(1 for s in specs if s.action == "device_halt")
    n_transient = sum(1 for s in specs
                      if s.action in ("raise", "corrupt"))
    if n_demote > len(rungs):
        raise SoakFailure(
            f"plan demotes {n_demote}x but only {len(rungs)} rungs "
            f"exist — an unabsorbable plan cannot gate exact counters")

    # run 1: clean reference, self-healing OFF
    off, sink_off, _, _ = _run(_base_cfg(
        tmp, n, "off", plan_ladder="off", device_reinit_max=0))
    # run 2: clean, self-healing ARMED — must change nothing
    on, sink_on, c_on, _ = _run(_base_cfg(tmp, n, "on"))
    # run 3: chaos
    chaos_cfg = _base_cfg(
        tmp, n, "chaos", fault_plan=pipe_plan,
        promote_after_segments=promote_after,
        device_reinit_max=max(1, n_halt),
        checkpoint_path=os.path.join(tmp, "chaos_ck.json"),
        telemetry_journal_path=os.path.join(tmp, "chaos.jsonl"))
    stats, sink, counters, unfired = _run(chaos_cfg)

    def check(cond, msg):
        if not cond:
            raise SoakFailure(msg)

    # zero-cost-off: arming the ladder on a clean run is bit-identical
    check(on.segments == off.segments,
          f"ladder-armed clean run segment count {on.segments} != "
          f"ladder-off {off.segments}")
    for i, (a, b) in enumerate(zip(sink_on.out, sink_off.out)):
        for x, y in zip(a[:3], b[:3]):
            check(np.array_equal(np.asarray(x), np.asarray(y)),
                  f"ladder-armed clean run differs at segment {i}: "
                  "arming self-healing must be bit-identical")
        check(a[3] == b[3], f"clean-run positive flag differs at {i}")
    check(c_on["plan_demotions"] == 0 and c_on["device_reinits"] == 0,
          "clean run recorded demotions/reinits")

    # chaos completed with accounted-only loss
    check(unfired == [], f"planned faults never fired: {unfired}")
    drained = len(sink.out)
    dropped = int(counters["segments_dropped"])
    check(drained + dropped == off.segments,
          f"loss not accounted: {drained} drained + {dropped} dropped "
          f"!= {off.segments} source segments")

    # recovered output parity: decisions exact, time series within the
    # demoted plans' documented tolerance (tests/test_fusion.py)
    for i, (a, b) in enumerate(zip(sink.out, sink_off.out)):
        check(np.array_equal(a[0], b[0]),
              f"segment {i}: signal_counts differ after recovery")
        check(np.array_equal(a[1], b[1]),
              f"segment {i}: zero_count differs after recovery")
        check(a[3] == b[3], f"segment {i}: positive flag differs")
        scale = float(np.abs(b[2]).max()) or 1.0
        if not np.allclose(a[2], b[2], rtol=0, atol=1e-3 * scale):
            raise SoakFailure(
                f"segment {i}: time series out of documented "
                f"tolerance after recovery (max delta "
                f"{float(np.abs(a[2] - b[2]).max()):.3g} vs atol "
                f"{1e-3 * scale:.3g})")

    # counters match the injected plan exactly
    check(int(counters["plan_demotions"]) == n_demote,
          f"plan_demotions {int(counters['plan_demotions'])} != "
          f"{n_demote} injected oom/compile faults")
    check(int(counters["device_reinits"]) == n_halt,
          f"device_reinits {int(counters['device_reinits'])} != "
          f"{n_halt} injected halts")
    check(int(counters["faults_injected"]) == len(specs),
          f"faults_injected {int(counters['faults_injected'])} != "
          f"{len(specs)} planned")
    check(int(counters["retries_total"]) >= n_transient,
          f"retries_total {int(counters['retries_total'])} < "
          f"{n_transient} injected transient faults")

    # phase 4: pool-scoped device halts — every scheduled halt drains
    # its member onto a survivor via live migration, losslessly and
    # bit-identically (migration stays at rung 0, so even the time
    # series is exact, unlike the demoted-plan tolerance above)
    pool_counters: dict = {}
    if pool_halts:
        pres, psink, pool_counters = _run_pool_phase(tmp, n, pool_halts)
        check(pres.status == "done",
              f"pool phase did not finish: {pres.status} "
              f"({pres.error!r})")
        check(len(psink.out) + pool_counters["segments_dropped"]
              == off.segments,
              f"pool phase loss not accounted: {len(psink.out)} "
              f"drained + {pool_counters['segments_dropped']} dropped "
              f"!= {off.segments} source segments")
        check(pool_counters["segments_dropped"] == 0,
              f"pool phase dropped "
              f"{pool_counters['segments_dropped']} segment(s) — a "
              "scoped halt migrates, it must not shed")
        for i, (a, b) in enumerate(zip(psink.out, sink_off.out)):
            check(np.array_equal(a[0], b[0])
                  and np.array_equal(a[1], b[1])
                  and np.array_equal(a[2], b[2]) and a[3] == b[3],
                  f"pool phase segment {i}: output differs from the "
                  "clean reference — migration must be bit-identical")
        check(pool_counters["device_drains"] == len(pool_halts),
              f"device_drains {pool_counters['device_drains']} != "
              f"{len(pool_halts)} scheduled pool halts")
        check(pool_counters["migrations"] >= len(pool_halts),
              f"migrations {pool_counters['migrations']} < "
              f"{len(pool_halts)} scheduled halts — a halt failed to "
              "drain its lane onto the survivor")
        check(pool_counters["device_reinits"] == 0,
              "a pool-scoped halt escalated to a fleet-wide reinit "
              "despite a healthy survivor")
        check(pool_counters["plan_demotions"] == 0,
              "the pool phase demoted a plan — migration must rejoin "
              "the survivor's family at rung 0")

    return {
        "seed": seed, "segments": int(off.segments), "plan": plan,
        "pool_halts": pool_halts,
        "pool_counters": pool_counters,
        "rungs": [r.step for r in rungs],
        "drained": drained, "dropped": dropped,
        "plan_demotions": int(counters["plan_demotions"]),
        "plan_promotions": int(counters["plan_promotions"]),
        "device_reinits": int(counters["device_reinits"]),
        "retries": int(counters["retries_total"]),
        "ok": True,
    }


def selftest(log2n: int = 12) -> list[str]:
    """Prove the gate catches what it exists to catch.  Probes (a)
    and (c) inject fault classes the armed machinery does NOT handle
    and demand the soak fails loudly; probe (b) proves the gate is
    not simply failing everything.  Returns failure strings (empty =
    the gate is sharp)."""
    failures = []
    # (a) an unhandled fault class: injected FATAL — no recovery
    # mechanism covers it, so the soak must NOT come back ok (either
    # the fatal escapes the pipeline or the gate flags the loss)
    try:
        run_soak(seed=1, segments=3, log2n=log2n,
                 plan="dispatch:fatal@1")
        failures.append(
            "gate passed a run with an injected FATAL fault — an "
            "unhandled fault class went unnoticed")
    except Exception:
        pass  # caught, as required
    # (b) sanity: one oom with healing armed must recover cleanly
    try:
        run_soak(seed=2, segments=3, log2n=log2n,
                 plan="dispatch:oom@1")
    except Exception as e:  # noqa: BLE001 - reported, not raised
        failures.append(f"single-oom probe did not recover with "
                        f"healing armed: {e!r}")
    # (c) a device fault with self-healing DISABLED must escalate —
    # device faults must never be swallowed when nothing handles them
    from srtb_tpu.io.synth import make_dispersed_baseband
    tmp = tempfile.mkdtemp(prefix="srtb_chaos_self_")
    n = 1 << log2n
    make_dispersed_baseband(n * 3, 1405.0, 64.0, SOAK_DM,
                            pulse_positions=n, nbits=8
                            ).tofile(os.path.join(tmp, "bb.bin"))
    try:
        _run(_base_cfg(tmp, n, "nh", plan_ladder="off",
                       device_reinit_max=0,
                       fault_plan="dispatch:oom@1"))
        failures.append(
            "an injected oom with self-healing DISABLED did not kill "
            "the run — device faults are being swallowed somewhere")
    except Exception:
        pass  # escalated, as required when healing is off
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="chaos-soak",
        description="seeded randomized device-fault soak "
                    "(see srtb_tpu/tools/chaos_soak.py)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--segments", type=int, default=6)
    ap.add_argument("--faults", type=int, default=4,
                    help="fault count for the generated plan")
    ap.add_argument("--plan", default=None,
                    help="explicit fault plan (overrides generation)")
    ap.add_argument("--log2n", type=int, default=14)
    ap.add_argument("--promote-after", type=int, default=0,
                    help="promotion probe after N healthy segments")
    ap.add_argument("--selftest", action="store_true",
                    help="prove the gate catches unhandled fault "
                         "classes")
    args = ap.parse_args(argv)

    if args.selftest:
        fails = selftest()
        for f in fails:
            print(f"chaos-soak selftest: {f}", file=sys.stderr)
        print("chaos-soak selftest: "
              + ("FAILED" if fails else
                 "OK — unhandled fault classes fail the gate"))
        return 1 if fails else 0

    try:
        report = run_soak(seed=args.seed, segments=args.segments,
                          faults=args.faults, log2n=args.log2n,
                          plan=args.plan,
                          promote_after=args.promote_after)
    except SoakFailure as e:
        print(json.dumps({"ok": False, "failure": str(e)}))
        print(f"chaos-soak: GATE FAILED — {e}", file=sys.stderr)
        return 1
    print(json.dumps(report, sort_keys=True))
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
