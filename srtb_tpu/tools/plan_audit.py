"""CLI driver for the compile-time HLO plan auditor.

Usage (CI runs exactly this, plus ``--selftest``)::

    python -m srtb_tpu.tools.plan_audit

AOT-lowers every plan family (``srtb_tpu/analysis/hlo_audit.py``),
audits the compiled artifacts — spectrum-sized HBM round trips,
donation/aliasing tables, f64/callback/collective/copy flags — and
diffs the resulting plan cards against the checked-in baseline
``srtb_tpu/analysis/plan_cards.json``.

Exit code 0 when every card matches the baseline and every invariant
check passes, 1 on any regression or failed check, 2 on usage errors.
Accept an intentional change with ``--write-baseline`` (per-plan notes
in the baseline's ``notes`` map are carried forward, same workflow as
srtb-lint).  Nothing executes on any device: the audit lowers and
compiles only, and runs on the CPU backend in CI.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _parse_plans(arg: str | None):
    from srtb_tpu.analysis.hlo_audit import PLAN_KEYS
    if not arg or arg == "all":
        return list(PLAN_KEYS)
    return [k.strip() for k in arg.split(",") if k.strip()]


def main(argv=None) -> int:
    from srtb_tpu.analysis import hlo_audit as HA

    ap = argparse.ArgumentParser(
        prog="plan-audit",
        description="compile-time HLO plan auditor "
                    "(see srtb_tpu/analysis/hlo_audit.py)")
    ap.add_argument("--plans", default="all",
                    help="comma-separated plan family keys (default all)")
    ap.add_argument("--log2n", type=int, default=HA.DEFAULT_LOG2N,
                    help="audit segment size exponent")
    ap.add_argument("--channels", type=int, default=HA.DEFAULT_CHANNELS,
                    help="audit spectrum_channel_count")
    ap.add_argument("--baseline", default=HA.DEFAULT_BASELINE,
                    help="plan-card baseline JSON (default: checked-in)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="skip the baseline diff (checks still gate)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept current cards into --baseline "
                         "(existing notes are kept)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--out", default="",
                    help="also write the full (informational) cards "
                         "to this JSON path")
    ap.add_argument("--list-plans", action="store_true")
    ap.add_argument("--selftest", action="store_true",
                    help="prove the auditor catches a dropped donation "
                         "and an injected extra spectrum pass")
    ap.add_argument("--verbose", "-v", action="store_true")
    args = ap.parse_args(argv)

    if args.list_plans:
        for spec in HA.PLAN_FAMILIES:
            print(f"{spec.key}: {spec.desc}")
        return 0

    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        print(f"plan-audit: note: auditing on backend {backend!r}; the "
              "checked-in baseline is a CPU-CI artifact", file=sys.stderr)

    if args.selftest:
        failures = HA.selftest(log2n=args.log2n, channels=args.channels)
        for f in failures:
            print(f"plan-audit selftest: {f}", file=sys.stderr)
        print("plan-audit selftest: "
              + ("FAILED" if failures else
                 "OK — dropped donation and injected extra spectrum "
                 "pass both move the audited cards"))
        return 1 if failures else 0

    try:
        keys = _parse_plans(args.plans)
        cards = HA.audit_families(keys, log2n=args.log2n,
                                  channels=args.channels)
    except KeyError as e:
        print(f"plan-audit: {e.args[0]}", file=sys.stderr)
        return 2

    if args.out:
        with open(args.out, "w") as f:
            json.dump({"cards": cards}, f, indent=2, sort_keys=True,
                      default=str)
            f.write("\n")

    bad_checks = HA.failed_checks(cards)

    if args.write_baseline:
        old = HA.CardBaseline.load(args.baseline)
        HA.CardBaseline.from_cards(cards, old=old).save(args.baseline)
        print(f"plan-audit: wrote {len(cards)} plan card(s) to "
              f"{args.baseline}")
        for c in bad_checks:
            print(f"plan-audit: warning: baselined with failing check "
                  f"-> {c}", file=sys.stderr)
        return 0

    regressions, new_plans, stale, ladder_failures = [], [], [], []
    if not args.no_baseline:
        baseline = HA.CardBaseline.load(args.baseline)
        regressions, new_plans, stale = HA.diff_cards(cards, baseline)
        if set(keys) == set(HA.PLAN_KEYS):
            # the self-healing demotion ladder must only land on
            # carded plan families — checked against the same baseline
            # the cards diff against, so a --write-baseline accepting
            # a new family also arms the ladder to use it.  Subset
            # runs skip it (same convention as staleness: a partial
            # baseline cannot judge the whole ladder).
            ladder_failures = HA.audit_ladder(
                baseline, log2n=args.log2n, channels=args.channels)
        else:
            stale = []  # subset runs cannot judge staleness

    problems = bad_checks + regressions + ladder_failures \
        + [f"{k}: not in baseline (run --write-baseline to accept)"
           for k in new_plans] \
        + [f"{k}: stale baseline entry (plan no longer audited)"
           for k in stale]

    if args.format == "json":
        print(json.dumps({
            "cards": {k: HA.stable_view(c) for k, c in cards.items()},
            "failed_checks": bad_checks,
            "regressions": regressions,
            "ladder_failures": ladder_failures,
            "new_plans": new_plans,
            "stale_baseline": stale,
        }, indent=2, sort_keys=True))
    else:
        for p in problems:
            print(p)
        if args.verbose:
            for k, c in sorted(cards.items()):
                progs = c["programs"]
                passes = "+".join(str(p["spectrum_passes"])
                                  for p in progs.values())
                don = {n: p["donation"] for n, p in progs.items()
                       if p["donation"]["declared"]}
                print(f"{k}: plan={c['plan_name']} "
                      f"audited={c['total_spectrum_passes']} ({passes}) "
                      f"donation={don if don else 'none'}")
        summary = (f"plan-audit: {len(cards)} plan(s), "
                   f"{len(bad_checks)} failed check(s), "
                   f"{len(regressions)} regression(s), "
                   f"{len(ladder_failures)} uncarded ladder target(s), "
                   f"{len(new_plans)} unbaselined, {len(stale)} stale")
        print(summary, file=sys.stderr if problems else sys.stdout)
    return 1 if problems else 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
